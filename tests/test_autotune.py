"""Plan autotuner (DESIGN.md §13): cost-model fitting, table
persistence/resolution, the knob chooser through ``make_plan(autotune=True)``
and the serving layer's shared cost model.

The calibration RUNNER (steady-state timing over the measurement grid) is
exercised ref-only here to keep the suite fast."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.batch.family import make_gaussian_family
from repro.core import VegasConfig
from repro.core.integrands import make_cosine, make_roos_arnold
from repro.engine import ExecutionConfig, PlanError, available, make_plan
from repro.engine import autotune as at


# --- fitting -----------------------------------------------------------------

def test_nnls_nonnegative():
    # A design whose plain OLS solution has a negative coefficient: the
    # active-set loop must drop it instead of returning it (monotone
    # predictions are the chooser's correctness condition).
    rng = np.random.default_rng(0)
    x = np.column_stack([np.ones(40), rng.uniform(1, 2, 40),
                         rng.uniform(1, 2, 40)])
    y = 2.0 + 3.0 * x[:, 1] - 0.5 * x[:, 2]      # truth has a negative term
    coef = at._nnls(x, y)
    assert (coef >= 0.0).all()
    assert coef[1] > 0.0


def test_fit_class_recovers_planted_coefficients():
    truth = at.ClassCoeffs(c_fixed=1e-3, c_eval_dim=2e-7, c_chunk=5e-4)
    samples = []
    for d in (4, 10):
        for n_cap in (16_384, 65_536, 131_072):
            for n_chunks in (4, 16, 64):
                samples.append(dict(
                    b=1, d=d, n_cap=n_cap, n_chunks=n_chunks, tile=None,
                    seconds=truth.fill_s(b=1, d=d, n_cap=n_cap,
                                         n_chunks=n_chunks)))
    fit = at.fit_class(samples)
    assert fit.c_fixed == pytest.approx(truth.c_fixed, rel=1e-6)
    assert fit.c_eval_dim == pytest.approx(truth.c_eval_dim, rel=1e-6)
    assert fit.c_chunk == pytest.approx(truth.c_chunk, rel=1e-6)
    assert fit.n_samples == len(samples)


def test_calibrate_ref_only_fits_and_saves(tmp_path):
    table = at.calibrate(fast=True, backends=("ref",), repeats=1)
    assert table.source == "calibrated"
    assert table.jax_backend == jax.default_backend()
    c = table.classes[at.class_key("ref")]   # device_kind-qualified
    assert c.n_samples >= 6
    for f in ("c_fixed", "c_eval_dim", "c_chunk", "c_tile_step",
              "iter_overhead_s"):
        assert getattr(c, f) >= 0.0
    # Measured fills take real time: the fit cannot be all-zero.
    assert c.fill_s(b=1, d=10, n_cap=1 << 17, n_chunks=8) > 0.0
    path = table.save(str(tmp_path / "COST_TABLE.json"))
    loaded = at.CostTable.load(path)
    assert loaded.classes[at.class_key("ref")] == c


# --- table persistence + resolution ------------------------------------------

def test_cost_table_roundtrip_and_fallbacks(tmp_path):
    table = at.CostTable(device_kind="cpu", jax_backend="cpu", git_sha="abc",
                         source="calibrated", calibration_wall_s=1.5,
                         classes={"ref": at.ClassCoeffs(c_fixed=0.5),
                                  "pallas|interpret":
                                      at.ClassCoeffs(c_chunk=0.25)})
    path = table.save(str(tmp_path / "t.json"))
    loaded = at.CostTable.load(path)
    assert loaded.source == path            # provenance tracks the file
    assert loaded.classes == dict(table.classes)
    # exact -> sibling mode -> builtin -> ref fallback chain
    assert loaded.coeffs("ref").c_fixed == 0.5
    assert loaded.coeffs("pallas|compiled").c_chunk == 0.25   # sibling
    assert (loaded.coeffs("pallas-fused|interpret")
            == at.BUILTIN_CLASSES["pallas-fused|interpret"])  # builtin
    assert loaded.coeffs("no-such-backend") == at.BUILTIN_CLASSES["ref"]


def test_resolve_table_priority(tmp_path, monkeypatch):
    explicit = at.CostTable(source="calibrated",
                            classes={"ref": at.ClassCoeffs(c_fixed=9.0)})
    assert at.resolve_table(explicit) is explicit
    p = explicit.save(str(tmp_path / "explicit.json"))
    assert at.resolve_table(p).coeffs("ref").c_fixed == 9.0
    with pytest.raises(OSError):
        at.resolve_table(str(tmp_path / "missing.json"))
    envt = at.CostTable(source="calibrated",
                        classes={"ref": at.ClassCoeffs(c_fixed=7.0)})
    monkeypatch.setenv(at.TABLE_ENV, envt.save(str(tmp_path / "env.json")))
    assert at.resolve_table(None).coeffs("ref").c_fixed == 7.0
    monkeypatch.delenv(at.TABLE_ENV)
    monkeypatch.chdir(tmp_path)             # no ./COST_TABLE.json here
    assert at.resolve_table(None) is at.BUILTIN_TABLE


# --- the knob chooser --------------------------------------------------------

def test_tune_reduces_ncap_padding_on_high_dim_shape():
    # roos_arnold d=10, neval=1e5: n_cubes=1024 so n_cap=102048; the default
    # chunk 16384 rounds n_cap up 12.4%, chunk 8192 only 4.4%.
    ig = make_roos_arnold()
    cfg = VegasConfig(neval=100_000, max_it=6, chunk=16_384,
                      execution=ExecutionConfig(autotune=True))
    plan = make_plan(ig, cfg)
    rep = plan.tuned
    assert rep is not None
    assert rep.class_key == at.class_key("ref")   # 'ref@<device_kind>'
    assert plan.cfg.chunk < 16_384
    assert plan.cfg.n_cap < 114_688          # strictly less padded
    assert rep.predicted_s <= rep.predicted_default_s
    assert not plan.execution.autotune       # knobs pinned: replan is cheap
    assert "autotuned[" in plan.describe()


def test_tuned_knobs_survive_replan_for_every_backend():
    # Acceptance: for EVERY registry backend, autotune=True yields a valid
    # plan whose chosen knobs, fed back through make_plan explicitly,
    # reproduce the same resolved geometry (the tuner emits nothing
    # make_plan would reject or renormalize).
    ig = make_cosine(dim=4)
    for backend in available():
        cfg = VegasConfig(neval=4_096, max_it=4, ninc=64,
                          execution=ExecutionConfig(backend=backend,
                                                    autotune=True))
        plan = make_plan(ig, cfg)
        assert plan.tuned is not None, backend
        replan = make_plan(ig, dataclasses.replace(
            VegasConfig(neval=4_096, max_it=4, ninc=64,
                        execution=plan.execution), chunk=plan.cfg.chunk))
        assert replan.cfg.chunk == plan.cfg.chunk, backend
        assert replan.execution.tile == plan.execution.tile, backend
        assert replan.backend.name == backend


def test_tune_family_and_batch_knob():
    fam = make_gaussian_family(np.linspace(0.2, 0.8, 4), dim=10)
    cfg = VegasConfig(neval=50_000, max_it=6, chunk=16_384,
                      execution=ExecutionConfig(autotune=True))
    plan = make_plan(fam, cfg)
    assert plan.tuned is not None
    assert plan.batched              # vmap predicted cheaper than serial
    assert plan.cfg.chunk < 16_384   # same padding win as the single run


def test_autotune_never_loses_an_admissible_plan():
    # Invalid pinned knobs surface make_plan's own PlanError — the tuner
    # must not launder tile=128 on 'ref' into a valid plan...
    ig = make_cosine(dim=4)
    with pytest.raises(PlanError):
        make_plan(ig, VegasConfig(
            neval=4_096, execution=ExecutionConfig(autotune=True, tile=128)))
    # ...and combos that succeed with explicit knobs also succeed tuned
    # (single + family, every backend).
    fam = make_gaussian_family(np.linspace(0.2, 0.8, 3), dim=4)
    for backend in available():
        for workload in (ig, fam):
            explicit = VegasConfig(neval=4_096, ninc=64, execution=
                                   ExecutionConfig(backend=backend))
            make_plan(workload, explicit)          # admissible baseline
            tuned = make_plan(workload, VegasConfig(
                neval=4_096, ninc=64,
                execution=ExecutionConfig(backend=backend, autotune=True)))
            assert tuned.tuned is not None, (backend, workload)


def test_tune_unknown_backend_defers_to_make_plan():
    ig = make_cosine(dim=4)
    cfg = VegasConfig(execution=ExecutionConfig(backend="cuda",
                                                autotune=True))
    with pytest.raises(PlanError, match="cuda"):
        make_plan(ig, cfg)


def test_tune_deterministic():
    ig = make_roos_arnold()
    cfg = VegasConfig(neval=100_000, max_it=6, chunk=16_384,
                      execution=ExecutionConfig(autotune=True))
    a, ra = at.tune(ig, cfg, table=at.BUILTIN_TABLE)
    b, rb = at.tune(ig, cfg, table=at.BUILTIN_TABLE)
    assert a.chunk == b.chunk
    assert dict(ra.chosen) == dict(rb.chosen)
    assert ra.predicted_s == rb.predicted_s


def test_explicit_cost_table_drives_the_choice(tmp_path):
    # A table where scan-step overhead dwarfs eval work must push the
    # chooser to the LARGEST chunk (fewest steps), the opposite of the
    # builtin table's padding-avoidance answer on the same shape.
    ig = make_roos_arnold()
    table = at.CostTable(source="calibrated", classes={
        "ref": at.ClassCoeffs(c_eval_dim=1e-12, c_chunk=1.0)})
    path = table.save(str(tmp_path / "t.json"))
    cfg = VegasConfig(neval=100_000, max_it=6, chunk=16_384,
                      execution=ExecutionConfig(autotune=True,
                                                cost_table=path))
    plan = make_plan(ig, cfg)
    # largest candidate that does not exceed the raw eval capacity
    # (neval + 2*n_cubes = 102048; 131072 is pure padding and filtered out)
    assert plan.cfg.chunk == 65_536
    assert plan.tuned.table_source == path


# --- prediction --------------------------------------------------------------

def test_prediction_monotone_in_neval():
    coeffs = at.BUILTIN_TABLE.coeffs("ref")
    cfg = VegasConfig(max_it=6, chunk=4_096)
    preds = [at.predict_run_s(coeffs, dataclasses.replace(
        cfg, neval=n).resolve(6)) for n in (10_000, 40_000, 160_000)]
    assert preds == sorted(preds)
    assert preds[0] < preds[-1]


def test_prediction_sharding_divides_fill_not_overhead():
    coeffs = at.ClassCoeffs(c_eval_dim=1e-7, c_chunk=1e-3,
                            iter_overhead_s=1e-2)
    rcfg = VegasConfig(neval=65_536, max_it=4, chunk=2_048).resolve(4)
    t1 = at.predict_run_s(coeffs, rcfg, n_shards=1)
    t4 = at.predict_run_s(coeffs, rcfg, n_shards=4)
    assert t4 < t1
    assert t4 > t1 / 4               # replicated adapt does not shrink


# --- the serving layer's shared cost model -----------------------------------

def test_online_cost_min_semantics_and_prior():
    table = at.CostTable(source="calibrated", classes={
        "ref": at.ClassCoeffs(c_fixed=1e-3, iter_overhead_s=2e-3)})
    cost = at.OnlineCost(table=table)
    rcfg = VegasConfig(neval=8_192, chunk=2_048).resolve(4)
    key = ("k",)
    # no observation yet: the table is the prior (needs the plan geometry)
    assert cost.unit(key) is None
    prior = cost.unit(key, rcfg=rcfg)
    assert prior == pytest.approx(
        table.coeffs("ref").iteration_s(
            b=1, d=rcfg.dim, n_cap=rcfg.n_cap,
            n_chunks=rcfg.n_cap // rcfg.chunk))
    # observations take over and keep the MINIMUM ever seen
    cost.observe(key, 0.5)
    cost.observe(key, 0.2)
    cost.observe(key, 0.9)
    assert cost.unit(key, rcfg=rcfg) == 0.2
    assert cost.classes_calibrated == 1
    assert cost.snapshot() == {"k": 0.2}
    # and without a table, unobserved classes stay uncalibrated (legacy)
    assert at.OnlineCost().unit(key, rcfg=rcfg) is None


def test_serve_consumes_table_as_budget_prior(tmp_path):
    from repro.serve import IntegrationRequest, SweepService
    # A table claiming ~1s per scenario-iteration: a 5ms budget must cap
    # the FIRST batch of a never-before-seen class at min_trips — before
    # any observation exists (the legacy model cannot cap batch one).
    table = at.CostTable(source="calibrated", classes={
        "ref": at.ClassCoeffs(c_fixed=1.0)})
    path = table.save(str(tmp_path / "t.json"))
    with SweepService(cost_table=path) as svc:
        assert svc.stats()["cost_model"]["table"] == path
        t = svc.submit(IntegrationRequest(
            family="gaussian", params=[0.5], neval=500, max_it=8, ninc=32,
            chunk=500, time_budget_s=5e-3, seed=0))
        r = t.result(timeout=120)
    assert r.capped
    assert int(r.n_it_used[0]) < 8


# --- steady-state program reuse ----------------------------------------------

def test_make_single_program_is_replayable():
    from repro.core import integrator as core
    from repro.engine.executor import make_single_program
    ig = make_cosine(dim=4)
    plan = make_plan(ig, VegasConfig(neval=4_096, max_it=4, ninc=64))
    prog = make_single_program(plan)
    state = core.init_state(ig, plan.cfg, jax.random.PRNGKey(0))
    out1 = prog(state)
    out2 = prog(state)               # non-donating: the input state survives
    np.testing.assert_array_equal(np.asarray(out1.results),
                                  np.asarray(out2.results))
    fam = make_gaussian_family(np.linspace(0.2, 0.8, 2), dim=2)
    with pytest.raises(ValueError, match="family"):
        make_single_program(make_plan(fam, VegasConfig(neval=2_048)))
