"""Unified execution engine (ISSUE 4): config split + deprecation shim,
capability-declaring backend registry, plan validation (loud PlanError
instead of tracer failures), and engine-vs-legacy path equivalence.

The multi-device half of the acceptance criteria — a sharded AND batched
run as one jitted program — lives in tests/_dist_worker.py (check 6), which
runs under 8 forced host devices."""

import dataclasses

import jax
import numpy as np
import pytest

import repro.engine as E
from repro.batch import run_batch, run_serial
from repro.batch.family import make_gaussian_family
from repro.core import VegasConfig, run
from repro.core import integrands as igs
from repro.launch.mesh import make_local_mesh

FAST = VegasConfig(neval=8_000, max_it=4, skip=1, ninc=32, chunk=2048)
KEY = jax.random.PRNGKey(5)


# --- VegasConfig split + deprecation shim ------------------------------------

def test_config_splits_algorithm_from_execution():
    cfg = VegasConfig()
    assert cfg.execution == E.ExecutionConfig()
    assert cfg.backend == "ref" and cfg.interpret is None and cfg.tile is None
    # algorithm fields are real dataclass fields; execution knobs are not
    names = {f.name for f in dataclasses.fields(cfg)}
    assert "backend" not in names and "execution" in names


def test_legacy_flat_fields_warn_and_fold_into_execution():
    with pytest.warns(DeprecationWarning, match="execution knobs moved"):
        cfg = VegasConfig(backend="pallas", fused_cubes=True, tile=64,
                          interpret=True)
    assert cfg.execution.backend == "pallas-fused"
    assert cfg.backend == "pallas-fused" and cfg.fused_cubes
    assert cfg.tile == 64 and cfg.interpret is True
    with pytest.warns(DeprecationWarning):
        cfg2 = VegasConfig(backend="pallas", fused_cubes=False)
    assert cfg2.execution.backend == "pallas" and not cfg2.fused_cubes
    with pytest.warns(DeprecationWarning):
        cfg3 = VegasConfig(backend="ref")
    assert cfg3.execution.backend == "ref"


def test_legacy_kwarg_never_upgrades_an_explicit_backend_choice():
    """Mixing one legacy kwarg (interpret) with an explicitly chosen
    registry backend must not remap 'pallas' (P-V2) to 'pallas-fused': the
    legacy fused default applies only when backend/fused_cubes themselves
    came in through the flat spelling."""
    with pytest.warns(DeprecationWarning):
        cfg = VegasConfig(interpret=True,
                          execution=E.ExecutionConfig(backend="pallas"))
    assert cfg.execution.backend == "pallas"
    assert cfg.interpret is True
    # fused_cubes=False alone downgrades a fused execution config
    with pytest.warns(DeprecationWarning):
        cfg2 = VegasConfig(fused_cubes=False,
                           execution=E.ExecutionConfig(backend="pallas-fused"))
    assert cfg2.execution.backend == "pallas"


def test_plan_accepts_any_dtype_spelling():
    """Every spelling jnp.dtype() accepts must validate like its canonical
    name (callers pre-engine passed np/jnp dtypes, not just strings)."""
    import jax.numpy as jnp
    for spelling in ("float32", "f4", np.float32, jnp.float32):
        E.make_plan(IG, dataclasses.replace(FAST, dtype=spelling))
    with pytest.raises(E.PlanError):
        E.make_plan(IG, dataclasses.replace(FAST, dtype=np.float64),
                    execution=E.ExecutionConfig(backend="pallas-fused"))


def test_config_rejects_unknown_kwargs_and_duplicates():
    with pytest.raises(TypeError, match="bogus"):
        VegasConfig(bogus=1)
    with pytest.raises(TypeError, match="duplicate"):
        VegasConfig(10_000, neval=20_000)


def test_dataclasses_replace_and_with_execution():
    cfg = dataclasses.replace(FAST, neval=123_000)
    assert cfg.neval == 123_000 and cfg.ninc == FAST.ninc
    assert cfg.execution == FAST.execution
    ex = E.ExecutionConfig(backend="pallas-fused", interpret=True)
    cfg2 = FAST.with_execution(ex)
    assert cfg2.execution is ex and cfg2.neval == FAST.neval


def test_shim_runs_identically_to_execution_config():
    """The legacy flat spelling and the ExecutionConfig spelling are the
    same program: bit-identical results."""
    ig = igs.make_cosine(dim=2)
    kw = dict(neval=6_000, max_it=3, ninc=16, chunk=2048)
    with pytest.warns(DeprecationWarning):
        legacy = VegasConfig(backend="pallas", interpret=True, **kw)
    new = VegasConfig(execution=E.ExecutionConfig(backend="pallas-fused",
                                                  interpret=True), **kw)
    r1 = run(ig, legacy, key=KEY)
    r2 = run(ig, new, key=KEY)
    assert r1.mean == r2.mean and r1.sdev == r2.sdev


# --- backend registry --------------------------------------------------------

def test_registry_declares_capability_matrix():
    assert set(E.available()) >= {"ref", "pallas", "pallas-fused"}
    ref = E.get_backend("ref")
    fused = E.get_backend("pallas-fused")
    assert ref.supports("shardable") and ref.supports("vmappable")
    assert fused.supports("in-kernel-rng") and not ref.supports("in-kernel-rng")
    assert fused.dtypes == ("float32",)
    text = E.capability_matrix()
    for name in E.available():
        assert name in text


def test_register_rejects_duplicates_and_unknown_capabilities():
    spec = E.get_backend("ref")
    with pytest.raises(ValueError, match="already registered"):
        E.register(spec)
    with pytest.raises(ValueError, match="unknown capabilities"):
        E.register(dataclasses.replace(
            spec, name="exotic", capabilities=frozenset({"warp-speed"})))
    assert "exotic" not in E.available()


# --- plan validation: loud PlanError, never a tracer failure -----------------

IG = igs.make_cosine(dim=2)


def test_plan_rejects_unknown_backend():
    with pytest.raises(E.PlanError, match="unknown fill backend.*registered"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(backend="cuda"))


@pytest.mark.parametrize("knob", [dict(tile=128), dict(interpret=True)],
                         ids=["tile", "interpret"])
def test_plan_rejects_knobs_the_backend_does_not_declare(knob):
    (name,) = knob
    with pytest.raises(E.PlanError, match=f"{name}.*not a knob.*'ref'"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(**knob))


@pytest.mark.parametrize("backend", ["pallas", "pallas-fused"])
def test_plan_rejects_unsupported_dtype(backend):
    cfg = dataclasses.replace(FAST, dtype="float64")
    with pytest.raises(E.PlanError, match="float32.*float64") as ei:
        E.make_plan(IG, cfg, execution=E.ExecutionConfig(backend=backend))
    # the reason is named only where the RNG runs inside the kernel
    assert ("in-kernel RNG" in str(ei.value)) == (backend == "pallas-fused")
    # the oracle declares f64 support: same plan, no error
    E.make_plan(IG, cfg, execution=E.ExecutionConfig(backend="ref"))


@pytest.mark.parametrize("mode", ["pathwise", "score"])
def test_plan_rejects_grad(mode):
    # pallas-fused declares neither grad capability
    with pytest.raises(E.PlanError, match=f"grad-{mode}"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(
            backend="pallas-fused", grad=E.GradPolicy(mode=mode)))


def test_plan_allows_vmap_shard_stop_and_knobs():
    fam = make_gaussian_family(np.array([0.3, 0.7]))
    plan = E.make_plan(fam, FAST, execution=E.ExecutionConfig(
        backend="pallas-fused", batch="vmap", tile=64,
        stop=E.StopPolicy(rtol=0.1)))
    assert plan.batched and plan.backend.name == "pallas-fused"
    assert plan.stop is not None and plan.execution.tile == 64
    plan = E.make_plan(IG, FAST, execution=E.ExecutionConfig(
        backend="pallas-fused", tile=64, mesh=make_local_mesh()))
    assert plan.backend.supports("shardable")
    assert plan.n_shards == jax.device_count()


def test_plan_rejects_vmap_of_a_plain_integrand():
    with pytest.raises(E.PlanError, match="IntegrandFamily"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(batch="vmap"))
    with pytest.raises(E.PlanError, match="batch='sideways'"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(batch="sideways"))


def test_plan_rejects_inconsistent_sharding():
    with pytest.raises(E.PlanError, match="without a mesh"):
        E.make_plan(IG, FAST,
                    execution=E.ExecutionConfig(shard_axes=("data",)))
    mesh = make_local_mesh()
    with pytest.raises(E.PlanError, match="not in mesh axes"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(
            mesh=mesh, shard_axes=("model",)))


def test_plan_rejects_checkpointing_a_family():
    fam = make_gaussian_family(np.array([0.3, 0.7]))
    with pytest.raises(E.PlanError, match="single-scenario"):
        E.make_plan(fam, FAST, execution=E.ExecutionConfig(
            checkpoint=E.CheckpointPolicy(directory="/tmp/x")))
    with pytest.raises(E.PlanError, match="directory or a callback"):
        E.make_plan(IG, FAST, execution=E.ExecutionConfig(
            checkpoint=E.CheckpointPolicy()))


def test_plan_describe_names_every_axis():
    fam = make_gaussian_family(np.array([0.3, 0.7]))
    plan = E.make_plan(fam, FAST, execution=E.ExecutionConfig(
        backend="pallas-fused", interpret=True))
    text = plan.describe()
    assert "pallas-fused" in text and "vmap B=2" in text
    assert "fori_loop" in text and "in-kernel-rng" in text


# --- executor composition ----------------------------------------------------

def test_engine_single_scenario_matches_core_run():
    plan = E.make_plan(IG, FAST)
    r_engine = E.execute(plan, key=KEY)
    r_run = run(IG, FAST, key=KEY)
    assert r_engine.mean == r_run.mean and r_engine.sdev == r_run.sdev


def test_single_device_mesh_plan_matches_unsharded():
    """A 1-device mesh resolves to n_shards=1 and must be the identical
    program (no shard_map wrapping, no kahan difference)."""
    mesh = make_local_mesh()
    plan = E.make_plan(IG, FAST, execution=E.ExecutionConfig(mesh=mesh))
    assert plan.n_shards == jax.device_count()
    if plan.n_shards == 1:
        r = E.execute(plan, key=KEY)
        assert r.mean == run(IG, FAST, key=KEY).mean


def test_family_serial_mode_matches_run_serial_bitwise():
    fam = make_gaussian_family(np.array([0.25, 0.75]))
    plan = E.make_plan(fam, FAST,
                       execution=E.ExecutionConfig(batch="serial"))
    assert plan.is_family and not plan.batched
    outs = E.execute(plan, key=KEY)
    base = run_serial(fam, FAST, key=KEY)
    assert [o.mean for o in outs] == [b.mean for b in base]


def test_run_batch_rejects_a_serial_plan():
    fam = make_gaussian_family(np.array([0.25, 0.75]))
    with pytest.raises(ValueError, match="vmapped path"):
        run_batch(fam, FAST, execution=E.ExecutionConfig(batch="serial"))


def test_family_rejects_state_resume():
    fam = make_gaussian_family(np.array([0.25, 0.75]))
    plan = E.make_plan(fam, FAST)
    st = run(IG, FAST, key=KEY).state
    with pytest.raises(ValueError, match="single-scenario"):
        E.execute(plan, key=KEY, state=st)


def test_checkpoint_policy_writes_and_resumes(tmp_path):
    """The checkpoint execution axis: a policy forces the host loop, writes
    retained checkpoints, and the restored state resumes to the same answer
    as the uninterrupted run."""
    from repro.dist.checkpoint import CheckpointManager
    cfg_half = dataclasses.replace(FAST, max_it=2).with_execution(
        E.ExecutionConfig(checkpoint=E.CheckpointPolicy(
            directory=str(tmp_path), keep=2)))
    run(IG, cfg_half, key=KEY)
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["ckpt_0.npz", "ckpt_1.npz"]

    full = run(IG, FAST, key=KEY)
    mgr = CheckpointManager(str(tmp_path))
    restored, step, _ = mgr.restore_latest(full.state)
    resumed = run(IG, FAST, key=KEY, state=restored)
    assert resumed.mean == pytest.approx(full.mean, rel=1e-6)


def test_checkpoint_policy_every_throttles(tmp_path):
    cfg = dataclasses.replace(FAST, max_it=4).with_execution(
        E.ExecutionConfig(checkpoint=E.CheckpointPolicy(
            directory=str(tmp_path), keep=10, every=2)))
    run(IG, cfg, key=KEY)
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["ckpt_1.npz", "ckpt_3.npz"]


def test_run_batch_through_engine_matches_serial():
    """The adapter chain (run_batch -> make_plan -> execute) preserves the
    batched-vs-serial stream parity contract."""
    fam = make_gaussian_family(np.linspace(0.3, 0.7, 3))
    batched = run_batch(fam, FAST, key=KEY)
    serial = run_serial(fam, FAST, key=KEY)
    for b in range(3):
        comb = float(np.hypot(batched.sdev[b], serial[b].sdev))
        assert abs(float(batched.mean[b]) - serial[b].mean) < 3 * comb


# --- entry-point defaults and the compile cache -----------------------------

def test_cli_and_requests_default_to_the_platform_backend(monkeypatch):
    from repro.engine import backends
    from repro.launch import env
    from repro.launch.integrate import main
    from repro.serve import IntegrationRequest
    monkeypatch.setattr(env, "use_compile_cache", lambda: None)
    assert IntegrationRequest(family="gaussian", params=[0.5]).backend == "auto"
    plan = main(["--integrand", "gaussian", "--neval", "1000", "--plan"])
    assert plan.backend.name == backends.backend_default()


def test_backend_default_registry_names():
    from repro.engine import backends
    assert backends.PLATFORM_BACKENDS == {"tpu": "pallas-fused"}
    assert backends.backend_default() == backends.PLATFORM_BACKENDS.get(
        jax.default_backend(), "ref")
    assert E.available() == ("pallas", "pallas-fused", "ref")


def test_auto_backend_resolves_in_plan():
    from repro.engine import backends
    plan = E.make_plan(IG, FAST, execution=E.ExecutionConfig(backend="auto"))
    assert plan.backend.name == backends.backend_default()
    assert plan.execution.backend == plan.backend.name  # recorded, not 'auto'
    # auto + autotune: the tuner sees the concrete backend
    plan = E.make_plan(IG, FAST, execution=E.ExecutionConfig(
        backend="auto", autotune=True))
    assert plan.tuned is not None
    assert plan.backend.name == backends.backend_default()


def test_use_compile_cache_leaves_a_set_directory_to_jax(monkeypatch):
    import pathlib

    from repro.launch import env
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert env.use_compile_cache() == "/elsewhere/cache" and calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert env.use_compile_cache() == str(env.CACHE_DIR)
    assert calls == [("jax_compilation_cache_dir", str(env.CACHE_DIR))]
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert env.CACHE_DIR == repo / ".jax_cache"
