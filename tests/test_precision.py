"""Precision-policy conformance and regression suite (DESIGN.md §15).

The planted-drift design: a CONSTANT integrand c on a uniform power-of-2
map makes every jacobian exactly 1.0 in f32, so each valid sample
contributes exactly ``fl32(c)`` and the true per-cube first moment is the
integer sample count times ``float64(fl32(c))`` — computable exactly, with
zero Monte Carlo noise.  Any deviation IS accumulation rounding.  The
per-cube counts are chosen non-power-of-2 (``neval = 4 * 32749``): with
power-of-2 counts of equal values, pairwise tree reduction is EXACT (every
partial sum is a power-of-2 multiple, and scaling by 2 is exact in
floating point), which silently zeroes the very drift being measured.

Expected ordering differs by where the backend widens (§15):

* ref widens the weights BEFORE the within-chunk sums, so
  f32 > Kahan > widened, and the widened error is exactly 0 here.
* pallas-fused keeps products AND the per-tile one-hot matmul's sums in
  f32 and widens the per-tile partial sums after — so Kahan and widening
  both eliminate only the cross-chunk error and share the same
  within-chunk floor: f32 > Kahan ~= widened.  The matmul sums the exact
  bf16 pieces of each weight (8 significand bits apiece, §7), so with
  2^k equal weights a tile that floor is 0: Kahan == widened == 0.
"""

import dataclasses
import os
import subprocess
import sys
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.engine as E
from repro.core import fill as fill_mod
from repro.core import integrator as I
from repro.core import map as vmap_
from repro.core import strat
from repro.core.integrands import make_cosine
from repro.engine import backends as backends_mod

D, NINC, NSTRAT = 2, 16, 2
N_CUBES = NSTRAT**D
C32 = np.float32(1 / 3)


@contextmanager
def _x64(flag: bool):
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", flag)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", old)


@pytest.fixture
def x64():
    with _x64(True):
        yield


class _Planted:
    lower = np.zeros(D)
    upper = np.ones(D)
    dim = D

    def __call__(self, x):
        return jnp.full(x.shape[:1], C32, jnp.float32)


def _planted_errors(fill_fn, neval, chunk, **extra):
    """max |cube_s1 - exact| for plain-f32 / Kahan / widened-f64 fills."""
    edges = vmap_.uniform_edges(np.zeros(D), np.ones(D), NINC, jnp.float32)
    n_h = strat.uniform_nh(neval, N_CUBES)
    n_cap = ((int(n_h.sum()) + chunk - 1) // chunk) * chunk
    kw = dict(nstrat=NSTRAT, n_cap=n_cap, chunk=chunk, dtype=jnp.float32,
              **extra)
    key = jax.random.PRNGKey(0)
    exact = np.asarray(n_h, np.float64) * np.float64(C32)
    out = {}
    for tag, kkw in [("f32", {}), ("kahan", dict(kahan=True)),
                     ("wide", dict(accum_dtype=jnp.float64))]:
        res = fill_fn(edges, n_h, key, _Planted(), **kw, **kkw)
        out[tag] = float(np.max(np.abs(
            np.asarray(res.cube_s1, np.float64) - exact)))
    return out


# --- conformance: planted-sum error ordering per backend ---------------------

def test_error_ordering_ref(x64):
    e = _planted_errors(fill_mod.fill_reference, neval=4 * 32749, chunk=128)
    assert e["f32"] > e["kahan"] > e["wide"], e
    # ref widens before the scatter: integer counts x one f32 value sum
    # exactly in f64.
    assert e["wide"] == 0.0, e


def test_error_ordering_fused_interpret(x64):
    # Power-of-2 neval here: per-chunk partials repeat identically, making
    # the within-chunk floor shared by Kahan and widening bit-identical.
    e = _planted_errors(fill_mod.fill_pallas, neval=1 << 17, chunk=128,
                        interpret=True, fused_cubes=True)
    # Products and the per-tile matmul's sums stay f32 (§15): widening
    # removes only the cross-chunk drift, exactly like Kahan — both beat
    # plain f32.  Each tile sums 2^k copies of the bf16 pieces of c, which
    # is exact, and so is their rebuild: the in-kernel floor is 0 here.
    assert e["f32"] > e["kahan"], e
    assert e["f32"] > e["wide"], e
    assert e["wide"] == 0.0, e
    assert abs(e["kahan"] - e["wide"]) <= 1e-12 * max(e["kahan"], 1.0), e


def test_pure_f64_reference_tiny(x64):
    """A pure-f64 run (sample AND accum float64, ref backend) sits far below
    every f32-sampled variant: the planted sum is exact to f64 rounding."""
    edges = vmap_.uniform_edges(np.zeros(D), np.ones(D), NINC, jnp.float64)
    n_h = strat.uniform_nh(4 * 32749, N_CUBES)
    n_cap = ((int(n_h.sum()) + 127) // 128) * 128
    res = fill_mod.fill_reference(edges, n_h, jax.random.PRNGKey(0),
                                  _Planted(), nstrat=NSTRAT, n_cap=n_cap,
                                  chunk=128, dtype=jnp.float64)
    exact = np.asarray(n_h, np.float64) * np.float64(C32)
    assert res.cube_s1.dtype == jnp.float64
    assert float(np.max(np.abs(np.asarray(res.cube_s1) - exact))) < 1e-8


def test_widened_fill_result_dtype(x64):
    """accum_dtype=f64 fills return f64 moments on every backend; the
    default policy still returns f32 (no silent promotion)."""
    for fn, extra in [(fill_mod.fill_reference, {}),
                      (fill_mod.fill_pallas,
                       dict(interpret=True, fused_cubes=True))]:
        edges = vmap_.uniform_edges(np.zeros(D), np.ones(D), NINC,
                                    jnp.float32)
        n_h = strat.uniform_nh(1024, N_CUBES)
        kw = dict(nstrat=NSTRAT, n_cap=1024, chunk=512, dtype=jnp.float32,
                  **extra)
        wide = fn(edges, n_h, jax.random.PRNGKey(0), _Planted(), **kw,
                  accum_dtype=jnp.float64)
        plain = fn(edges, n_h, jax.random.PRNGKey(0), _Planted(), **kw)
        for leaf in jax.tree.leaves(wide):
            assert leaf.dtype == jnp.float64, fn
        for leaf in jax.tree.leaves(plain):
            assert leaf.dtype == jnp.float32, fn


def test_return_comp_requires_kahan():
    edges = vmap_.uniform_edges(np.zeros(D), np.ones(D), NINC, jnp.float32)
    n_h = strat.uniform_nh(1024, N_CUBES)
    kw = dict(nstrat=NSTRAT, n_cap=1024, chunk=512, dtype=jnp.float32)
    with pytest.raises(ValueError, match="kahan"):
        fill_mod.fill_reference(edges, n_h, jax.random.PRNGKey(0),
                                _Planted(), **kw, return_comp=True)
    out, comp = fill_mod.fill_reference(edges, n_h, jax.random.PRNGKey(0),
                                        _Planted(), **kw, kahan=True,
                                        return_comp=True)
    assert jax.tree.structure(out) == jax.tree.structure(comp)


# --- plan validation: the PlanError matrix -----------------------------------

def _cfg(backend, accum=None, sample=None, dtype="float32", **exec_kw):
    prec = (E.PrecisionPolicy(sample_dtype=sample, accum_dtype=accum)
            if (accum or sample) else None)
    return I.VegasConfig(
        neval=4096, max_it=2, skip=1, ninc=32, chunk=1024, dtype=dtype,
        execution=E.ExecutionConfig(backend=backend, precision=prec,
                                    **exec_kw))


def test_plan_rejects_f64_samples_on_kernel_backends():
    with _x64(True):
        for backend in ("pallas", "pallas-fused"):
            with pytest.raises(E.PlanError, match="supports dtypes"):
                E.make_plan(make_cosine(dim=2), _cfg(backend,
                                                     dtype="float64"))


def test_plan_rejects_unsupported_precision_pair(monkeypatch):
    spec = backends_mod.get("ref")
    monkeypatch.setitem(
        backends_mod._REGISTRY, "ref",
        dataclasses.replace(spec, precisions=(("float32", "float32"),)))
    with _x64(True):
        with pytest.raises(E.PlanError, match="precision pairs"):
            E.make_plan(make_cosine(dim=2), _cfg("ref", accum="float64"))


def test_plan_rejects_sample_dtype_conflict():
    with pytest.raises(E.PlanError, match="conflicts with cfg.dtype"):
        E.make_plan(make_cosine(dim=2), _cfg("ref", sample="float64"))


def test_plan_rejects_widened_accum_without_x64():
    with _x64(False):
        with pytest.raises(E.PlanError, match="needs x64 enabled"):
            E.make_plan(make_cosine(dim=2), _cfg("ref", accum="float64"))


def test_plan_rejects_grad_with_widened_accum(x64):
    with pytest.raises(E.PlanError, match="grad \\+ widened"):
        E.make_plan(make_cosine(dim=2),
                    _cfg("ref", accum="float64",
                         grad=E.GradPolicy(mode="pathwise")))


def test_plan_accepts_widened_and_narrowed_policies(x64):
    # f32 samples -> f64 accumulators on every kernel backend.
    for backend in ("ref", "pallas", "pallas-fused"):
        kw = {} if backend == "ref" else dict(interpret=True)
        plan = E.make_plan(make_cosine(dim=2),
                           _cfg(backend, accum="float64", **kw))
        assert plan.precision.widened
        assert "float32->float64" in plan.describe()
    # ...and ref also accepts the narrowing direction (f64 -> f32).
    plan = E.make_plan(make_cosine(dim=2),
                       _cfg("ref", accum="float32", dtype="float64"))
    assert not plan.precision.widened
    assert "float64->float32" in plan.describe()


def test_widened_plan_end_to_end(x64):
    """pallas-fused accepts and executes accum_dtype=float64 plans
    (interpret mode); estimates stay sane."""
    ig = make_cosine(dim=2)
    for backend in ("ref", "pallas-fused"):
        kw = {} if backend == "ref" else dict(interpret=True)
        plan = E.make_plan(ig, _cfg(backend, accum="float64", **kw))
        res = E.execute(plan, key=jax.random.PRNGKey(3))
        assert np.isfinite(res.mean) and np.isfinite(res.sdev)
        assert abs(res.mean - ig.target) < max(5 * res.sdev, 5e-2), \
            (backend, res.mean, ig.target)


def test_loop_carry_stays_in_sample_dtype(x64):
    """Widened moments must not promote the loop-carried state: adapted
    edges (next iteration's samples) are cast back to the sample dtype."""
    ig = make_cosine(dim=2)
    rc = _cfg("ref", accum="float64").resolve(ig.dim)
    st = I.init_state(ig, rc, jax.random.PRNGKey(0))
    st2 = I.iteration_step(st, ig, rc)
    assert st2.edges.dtype == jnp.float32
    assert st2.results.dtype == jnp.float32


# --- autotuner budget: 8-byte accumulators shrink the candidate sets ---------

def test_valid_tiles_shrink_under_f64_accum():
    from repro.kernels.ops import valid_tiles
    kw = dict(chunk=4096, d=4, ninc=1024, n_cubes=1 << 18)
    t32 = valid_tiles(**kw, accum_itemsize=4)
    t64 = valid_tiles(**kw, accum_itemsize=8)
    assert set(t64) < set(t32), (t32, t64)
    assert max(t64) < max(t32), (t32, t64)


def test_autotune_prices_accum_itemsize():
    from repro.engine.autotune import _accum_itemsize
    assert _accum_itemsize(E.ExecutionConfig()) == 4
    assert _accum_itemsize(E.ExecutionConfig(
        precision=E.PrecisionPolicy(accum_dtype="float64"))) == 8


# --- satellite regressions ---------------------------------------------------

def test_serve_normalizes_params_to_request_dtype():
    """Regression: _norm_1d/_norm_2d coerced params to float64
    unconditionally; a float64 param array closed over by the family would
    promote every fill product behind the plan's back."""
    from repro.serve import IntegrationRequest, SweepService
    from repro.serve.service import _norm_1d

    assert _norm_1d([0.5]).dtype == np.float64        # default unchanged
    assert _norm_1d([0.5], np.float32).dtype == np.float32

    svc = SweepService()
    for want in ("float32", "float64"):
        req = IntegrationRequest(family="gaussian", params=[0.3, 0.5],
                                 dtype=want)
        _, params, cfg = svc._resolve(req)
        # The normalized array the family builder receives carries the
        # REQUEST's dtype, not a hardwired float64.  (What the builder then
        # does with it is the family's own contract.)
        assert params.dtype == np.dtype(want), (want, params.dtype)

    # ...and the request's accum_dtype lands in the plan's PrecisionPolicy.
    req = IntegrationRequest(family="gaussian", params=[0.3],
                             accum_dtype="float64")
    _, _, cfg = svc._resolve(req)
    assert cfg.execution.precision.accum_dtype == "float64"
    assert req.compat_key() != dataclasses.replace(
        req, accum_dtype=None).compat_key()


def test_sharded_fill_subtracts_psummed_compensation(monkeypatch):
    """Regression: the sharded combination psummed the Kahan accumulators
    and threw the compensations away.  Drive `make_sharded_fill` with a
    fake backend fill producing a known (part, comp) pair: the combined
    result must be part - comp (the corrected total), not part — and the
    builder must have asked the backend for the compensation at all."""
    from repro.core.fill import FillResult

    ig = make_cosine(dim=2)
    rc = _cfg("ref").resolve(ig.dim)
    n_cubes = rc.nstrat**ig.dim
    part = FillResult(jnp.full((ig.dim, rc.ninc), 2.0),
                      jnp.full((ig.dim, rc.ninc), 8.0),
                      jnp.full((n_cubes,), 4.0), jnp.full((n_cubes,), 6.0))
    comp = jax.tree.map(lambda x: jnp.full_like(x, 0.25), part)
    seen = {}

    def fake_bind_fill(rcfg, backend=None, **overrides):
        seen.update(overrides)
        return lambda e, nh, k, integ, **kw: (part, comp)

    from repro.engine import sharding as sharding_mod
    monkeypatch.setattr(sharding_mod.backends_mod, "bind_fill",
                        fake_bind_fill)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    fill = sharding_mod.make_sharded_fill(mesh, ("data",), rc)
    assert seen.get("kahan") and seen.get("return_comp"), seen
    got = fill(jnp.zeros((ig.dim, rc.ninc + 1)), jnp.ones((n_cubes,)),
               jax.random.PRNGKey(0), ig)
    want = jax.tree.map(jnp.subtract, part, comp)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_kahan_shard_invariance_subprocess():
    """Regression: with the compensation carried through the psum, the
    sharded fill on ANY device count stays within a few ulps of the f64
    ground truth — and 1/2/4-shard results agree with each other at that
    floor.  Run under 4 forced host devices in a subprocess so the device
    count never leaks into this process."""
    worker = os.path.join(os.path.dirname(__file__), "_precision_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, worker], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ALL_OK" in out.stdout, out.stdout


def test_checkpoint_restore_across_x64_flip(tmp_path):
    """Regression: a checkpoint written under JAX_ENABLE_X64=1 (f64 leaves)
    must restore into an x64-off process — leaves are cast to the
    template's dtypes instead of crashing the donated-buffer resume."""
    from repro.dist import checkpoint as CK
    tree64 = {"edges": np.linspace(0.0, 1.0, 9, dtype=np.float64),
              "it": np.int64(4)}
    p = str(tmp_path / "c.npz")
    CK.save(p, tree64, step=4)
    like = {"edges": jnp.zeros(9, jnp.float32), "it": jnp.array(0, jnp.int32)}
    back, step, _ = CK.restore(p, like)
    assert step == 4
    assert back["edges"].dtype == jnp.float32
    assert back["it"].dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(back["edges"]),
                               tree64["edges"].astype(np.float32))


def test_checkpoint_restore_rejects_kind_mismatch(tmp_path):
    """A float-vs-int flip is NOT an x64 flip: refuse with an error naming
    the offending leaf rather than silently casting across kinds."""
    from repro.dist import checkpoint as CK
    p = str(tmp_path / "c.npz")
    CK.save(p, {"a": np.float32(1.5), "b": np.arange(3, dtype=np.int32)},
            step=0)
    like = {"a": jnp.array(0.0, jnp.float32), "b": jnp.zeros(3, jnp.float32)}
    with pytest.raises(ValueError, match="different kinds") as ei:
        CK.restore(p, like)
    assert "'b'" in str(ei.value) or "b" in str(ei.value)
