"""P-V3 fused streaming fill: RNG contract, fused-kernel oracle parity,
memory-footprint (jaxpr) checks, and interpret-mode autodetection.

The headline invariants of the fused path (kernels/vegas_fill.py,
DESIGN.md §7):
  * in-kernel uniforms == ``jax.random.uniform(fold_in(key, g), (chunk, d))``
    BIT-FOR-BIT, under both threefry counter layouts;
  * no per-eval float array exists anywhere in the traced program — HBM
    traffic is the sorted int32 cube-id input plus O(accumulators);
  * FillResults match ``fill_reference`` at the standard parity tolerances
    (exercised by tests/test_fill_parity.py's three-way sweep).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

import repro.kernels as K
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import vegas_fill as vk


def _ig(x):
    return jnp.sum(x * x, axis=-1) + 1.0


# ---------------------------------------------------------------------------
# RNG contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk,d,tile", [
    (256, 4, 64),     # pow2 everything
    (100, 3, 50),     # nothing a power of two
    (96, 2, 96),      # single tile == chunk
    (25, 3, 25),      # chunk*d odd: the padded-counter path
    (512, 1, 128),    # d=1
])
@pytest.mark.parametrize("partitionable", [True, False])
def test_inkernel_uniforms_bitexact(chunk, d, tile, partitionable):
    """In-kernel tile uniforms reassemble to uniform(fold_in(key, g)) exactly
    (not allclose: np.array_equal on the raw f32 bits)."""
    old = bool(jax.config.jax_threefry_partitionable)
    jax.config.update("jax_threefry_partitionable", partitionable)
    try:
        key = jax.random.PRNGKey(7)
        for g in (0, 5):
            k = jax.random.fold_in(key, g)
            expected = jax.random.uniform(k, (chunk, d), dtype=jnp.float32)
            got = vk.chunk_uniforms(kops.key_bits(k), chunk=chunk, d=d,
                                    tile=tile)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(expected))
    finally:
        jax.config.update("jax_threefry_partitionable", old)


def test_inkernel_uniforms_tile_invariant():
    """The tile decomposition does not change the stream: any tile size that
    divides the chunk reproduces the same (chunk, d) block."""
    key = jax.random.fold_in(jax.random.PRNGKey(3), 2)
    kb = kops.key_bits(key)
    whole = vk.chunk_uniforms(kb, chunk=240, d=3)
    for tile in (240, 120, 80, 48, 16):
        np.testing.assert_array_equal(
            np.asarray(vk.chunk_uniforms(kb, chunk=240, d=3, tile=tile)),
            np.asarray(whole))


def test_typed_key_bits_roundtrip():
    """key_bits handles both legacy raw and new-style typed keys."""
    raw = jax.random.PRNGKey(11)
    typed = jax.random.key(11)
    np.testing.assert_array_equal(np.asarray(kops.key_bits(raw)),
                                  np.asarray(kops.key_bits(typed)))


# ---------------------------------------------------------------------------
# Fused kernel vs oracle (sorted ids, masked tail, odd n_cubes)
# ---------------------------------------------------------------------------

def _sorted_inputs(key, chunk, d, ninc, nstrat, n_live):
    """Sorted cube ids with a masked overflow tail, as ops.fill produces."""
    n_cubes = nstrat**d
    ids = jnp.sort(jax.random.randint(key, (n_live,), 0, n_cubes,
                                      dtype=jnp.int32))
    cube = jnp.concatenate(
        [ids, jnp.full((chunk - n_live,), n_cubes, jnp.int32)])
    # dtype pinned: the fused path is f32-only (RNG contract), and under
    # JAX_ENABLE_X64=1 the float defaults here would silently become f64.
    w = jax.random.uniform(jax.random.fold_in(key, 1), (d, ninc),
                           minval=0.05, maxval=1.0, dtype=jnp.float32)
    w = w / w.sum(1, keepdims=True)
    edges_lo = jnp.concatenate(
        [jnp.zeros((d, 1), jnp.float32), jnp.cumsum(w, 1)[:, :-1]], axis=1)
    return cube.reshape(chunk, 1), edges_lo, w, n_cubes


@pytest.mark.parametrize("chunk,d,ninc,nstrat,tile,n_live", [
    (256, 3, 32, 3, 128, 200),    # n_cubes=27: far from a tile multiple
    (256, 2, 64, 5, 64, 256),     # no masked tail
    (384, 4, 50, 2, 96, 120),     # mostly masked; ninc not a power of two
    (128, 1, 16, 7, 128, 100),    # d=1
])
def test_fused_kernel_matches_oracle(chunk, d, ninc, nstrat, tile, n_live):
    """vegas_fill_fused == fused oracle when fed identical uniforms.

    Note: random sorted ids may repeat a cube more than ``tile`` times but
    never skip backwards, so each tile still touches a contiguous id window —
    the same invariant ops.fill's searchsorted ids satisfy.
    """
    key = jax.random.PRNGKey(chunk + d)
    cube, edges_lo, widths, n_cubes = _sorted_inputs(
        key, chunk, d, ninc, nstrat, n_live)
    k = jax.random.fold_in(key, 9)
    u = vk.chunk_uniforms(kops.key_bits(k), chunk=chunk, d=d)
    ms_r, mc_r, s1_r, s2_r = kref.vegas_fill_fused_ref(
        u, cube, edges_lo, widths, nstrat=nstrat, n_cubes=n_cubes,
        integrand=_ig)
    ms, mc, s1p, s2p = vk.vegas_fill_fused(
        kops.key_bits(k).reshape(1, 2), cube, edges_lo, widths,
        nstrat=nstrat, n_cubes=n_cubes, integrand=_ig, tile=tile,
        interpret=True)
    s1 = s1p.reshape(-1)[:n_cubes]
    s2 = s2p.reshape(-1)[:n_cubes]
    for got, want, tag in [(ms, ms_r, "ms"), (mc, mc_r, "mc"),
                           (s1, s1_r, "s1"), (s2, s2_r, "s2")]:
        scale = float(np.abs(np.asarray(want)).max()) or 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5 * scale, err_msg=tag)
    # the pad region beyond n_cubes holds only clipped zero contributions
    assert float(jnp.abs(s1p.reshape(-1)[n_cubes:]).max(initial=0.0)) == 0.0


def test_fused_kernel_all_masked():
    """Every eval in the overflow bucket -> all accumulators exactly zero."""
    chunk, d, ninc, nstrat = 128, 2, 16, 3
    cube, edges_lo, widths, n_cubes = _sorted_inputs(
        jax.random.PRNGKey(0), chunk, d, ninc, nstrat, n_live=0)
    ms, mc, s1p, s2p = vk.vegas_fill_fused(
        kops.key_bits(jax.random.PRNGKey(1)).reshape(1, 2), cube, edges_lo,
        widths, nstrat=nstrat, n_cubes=n_cubes, integrand=_ig, tile=64,
        interpret=True)
    for a in (ms, mc, s1p, s2p):
        assert float(jnp.abs(a).max()) == 0.0


# ---------------------------------------------------------------------------
# Memory footprint: the fused jaxpr has no per-eval float array
# ---------------------------------------------------------------------------

def _float_dims(jaxpr, dims):
    """Collect every dimension of every float aval in jaxpr, recursively
    (scan bodies, pallas kernel jaxprs, closed calls)."""
    from jax.extend.core import Jaxpr, ClosedJaxpr

    def visit(p):
        if isinstance(p, ClosedJaxpr):
            visit(p.jaxpr)
            return
        if not isinstance(p, Jaxpr):
            if isinstance(p, (list, tuple)):
                for x in p:
                    visit(x)
            elif isinstance(p, dict):
                for x in p.values():
                    visit(x)
            return
        for eqn in p.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if (aval is not None and hasattr(aval, "shape")
                        and hasattr(aval, "dtype")
                        and jnp.issubdtype(aval.dtype, jnp.floating)):
                    dims.update(aval.shape)
            for param in eqn.params.values():
                visit(param)

    visit(jaxpr)
    return dims


def _fill_jaxpr(fused: bool, *, chunk=2048, n_chunks=4, d=2, ninc=32,
                nstrat=3, rng_in_kernel=None):
    from repro.core import map as vmap_
    from repro.core import strat
    n_cubes = nstrat**d
    n_cap = chunk * n_chunks
    edges = vmap_.uniform_edges([0.0] * d, [1.0] * d, ninc)
    n_h = strat.uniform_nh(n_cap - n_cubes, n_cubes)
    closed = jax.make_jaxpr(
        lambda e, nh, k: kops.fill(e, nh, k, _ig, nstrat=nstrat, n_cap=n_cap,
                                   chunk=chunk, interpret=True,
                                   fused_cubes=fused, tile=256,
                                   rng_in_kernel=rng_in_kernel))(
        edges, n_h, jax.random.PRNGKey(0))
    return closed, chunk, n_cap


def test_fused_jaxpr_has_no_per_eval_float_array():
    """Acceptance check on the streaming program (in-kernel RNG, what runs
    compiled on TPU): NO float array with a dimension at chunk scale or above
    exists — neither the (chunk, d) uniforms nor the (chunk, 1) weight output
    survive the fusion (the only chunk-sized array left is the int32 cube-id
    input).  The baseline program, by contrast, still materializes both."""
    fused, chunk, n_cap = _fill_jaxpr(fused=True, rng_in_kernel=True)
    dims = _float_dims(fused.jaxpr, set())
    assert max(dims) < chunk, f"per-eval float array leaked: dims={dims}"

    baseline, chunk, n_cap = _fill_jaxpr(fused=False)
    dims_b = _float_dims(baseline.jaxpr, set())
    assert max(dims_b) >= chunk, "baseline should materialize per-chunk floats"


def test_fused_hybrid_jaxpr_has_no_weight_output():
    """The interpret-mode hybrid (uniforms precomputed per chunk, everything
    else fused) still has no per-eval WEIGHT array: its only chunk-sized
    float is the uniforms input block."""
    hybrid, chunk, n_cap = _fill_jaxpr(fused=True, rng_in_kernel=False)
    dims = _float_dims(hybrid.jaxpr, set())
    assert max(dims) <= chunk, f"beyond-chunk float array leaked: dims={dims}"
    # chunk-sized floats exist (u) but only with the d-column shape — the
    # (chunk, 1) weight output shape must be gone.
    shapes = set()

    from jax.extend.core import Jaxpr, ClosedJaxpr

    def visit(p):
        if isinstance(p, ClosedJaxpr):
            return visit(p.jaxpr)
        if isinstance(p, (list, tuple)):
            return [visit(x) for x in p]
        if isinstance(p, dict):
            return [visit(x) for x in p.values()]
        if not isinstance(p, Jaxpr):
            return
        for eqn in p.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if (aval is not None and getattr(aval, "shape", None)
                        and hasattr(aval, "dtype")
                        and jnp.issubdtype(aval.dtype, jnp.floating)):
                    shapes.add(tuple(aval.shape))
            for param in eqn.params.values():
                visit(param)

    visit(hybrid.jaxpr)
    assert (chunk, 1) not in shapes, "per-eval weight array leaked"


def test_fused_jaxpr_no_ncap_array_any_dtype():
    """Scan-chunking keeps every array but the range's int32 cube ids
    below n_cap: those cost 4 bytes a lane, built once per fill; uniforms
    and weights live one chunk at a time (DESIGN.md §P-V2)."""
    from jax.extend.core import Jaxpr, ClosedJaxpr

    closed, chunk, n_cap = _fill_jaxpr(fused=True)
    wide = set()

    def visit(p):
        if isinstance(p, ClosedJaxpr):
            return visit(p.jaxpr)
        if isinstance(p, (list, tuple)):
            return [visit(x) for x in p]
        if isinstance(p, dict):
            return [visit(x) for x in p.values()]
        if not isinstance(p, Jaxpr):
            return
        for eqn in p.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", None)
                if shape and max(shape) >= n_cap:
                    wide.add(jnp.dtype(aval.dtype))
            for param in eqn.params.values():
                visit(param)

    visit(closed.jaxpr)
    assert wide <= {jnp.dtype(jnp.int32)}, f"n_cap-sized array leaked: {wide}"


# ---------------------------------------------------------------------------
# One-hot contractions: one exact bf16 MXU pass (DESIGN.md D1/D2)
# ---------------------------------------------------------------------------

def _signed_binades(key, shape, lo_exp, hi_exp):
    """f32 values with random signs, magnitudes spread log-uniformly over
    10**lo_exp .. 10**hi_exp, and random mantissas."""
    k1, k2 = jax.random.split(key)
    mag = 10.0 ** jax.random.uniform(k1, shape, minval=lo_exp, maxval=hi_exp)
    sign = jnp.where(jax.random.bernoulli(k2, 0.5, shape), -1.0, 1.0)
    return (sign * mag).astype(jnp.float32)


def test_split_gather_returns_table_bit_for_bit():
    """The gather of the split (6d, ninc) table rebuilds every entry exactly:
    edges of both signs over 60 decades, widths down to 1e-30, zeros, and
    values one ulp under a power of two (every mantissa bit set)."""
    d, ninc, n = 3, 256, 512
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(18), 3)
    edges = _signed_binades(k1, (d, ninc), -30, 30)
    widths = jnp.abs(_signed_binades(k2, (d, ninc), -30, 30))
    powers = np.float32(2.0) ** np.arange(-20, 20, dtype=np.float32)
    full = np.nextafter(powers, np.float32(0))
    edges = edges.at[:, :8].set(0.0).at[:, 8:48].set(-full)
    widths = widths.at[:, :8].set(1e-30).at[:, 8:16].set(0.0)
    widths = widths.at[:, 16:56].set(full)
    # every slot at least once, then random ones
    idx = jnp.concatenate(
        [jnp.tile(jnp.arange(ninc, dtype=jnp.int32)[:, None], (1, d)),
         jax.random.randint(k3, (n - ninc, d), 0, ninc, jnp.int32)])

    def body(idx_ref, table_ref, out_ref):
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, ninc), 1)
        cols = []
        for k in range(d):
            oh = (idx_ref[:, k:k + 1] == lanes).astype(jnp.bfloat16)
            cols += vk._gather_map(oh, table_ref, k)
        out_ref[...] = jnp.concatenate(cols, axis=1)

    out = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((n, 2 * d), jnp.float32),
        interpret=True)(idx, vk._split_table(edges, widths))
    out = np.asarray(out)
    for k in range(d):
        ik = np.asarray(idx[:, k])
        for col, table in ((2 * k, edges), (2 * k + 1, widths)):
            np.testing.assert_array_equal(
                out[:, col].view(np.uint32),
                np.asarray(table)[k, ik].view(np.uint32))


def test_split_sums_match_f64_to_f32_rounding():
    """The map histogram (w2 split, count exact) and the cube moments (w and
    w2 split) through one bf16 pass each agree with an f64 sum of the same
    f32 products to f32 rounding; the counts are exact.  A bf16 operand
    (the contraction without the split) is off by ~2**-9 relative."""
    n, slots = 512, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(19), 3)
    ids = jax.random.randint(k1, (n, 1), 0, slots, jnp.int32)
    w = _signed_binades(k2, (n, 1), -15, 15)
    cnt = jax.random.bernoulli(k3, 0.7, (n, 1)).astype(jnp.float32)

    def body(ids_ref, w_ref, cnt_ref, out_ref):
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, slots), 1)
        oh = (ids_ref[...] == lanes).astype(jnp.bfloat16)
        w = w_ref[...]
        w2 = w * w
        s1, s2 = vk._onehot_sums(vk._stack(split=(w, w2)), oh, 2)
        ms, mc = vk._onehot_sums(
            vk._stack(split=(w2,), exact=(cnt_ref[...],)), oh, 1)
        out_ref[...] = jnp.concatenate([s1, s2, ms, mc], axis=0)

    got = np.asarray(pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((4, slots), jnp.float32),
        interpret=True)(ids, w, cnt)).astype(np.float64)
    i = np.asarray(ids).ravel()
    w32 = np.asarray(w).ravel()
    w64, w2_64 = w32.astype(np.float64), (w32 * w32).astype(np.float64)
    eps = float(np.finfo(np.float32).eps)
    for row, vals in ((0, w64), (1, w2_64), (2, w2_64)):
        want = np.bincount(i, vals, slots)
        bound = 8 * eps * np.bincount(i, np.abs(vals), slots)
        assert (np.abs(got[row] - want) <= bound).all(), row
    np.testing.assert_array_equal(
        got[3], np.bincount(i, np.asarray(cnt).ravel(), slots))


def _dot_generals(jaxpr):
    """Every dot_general eqn inside the pallas_call kernel bodies of
    ``jaxpr``, recursively."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = []

    def visit(p, in_kernel):
        if isinstance(p, ClosedJaxpr):
            return visit(p.jaxpr, in_kernel)
        if isinstance(p, (list, tuple)):
            return [visit(x, in_kernel) for x in p]
        if not isinstance(p, Jaxpr):
            return
        for eqn in p.eqns:
            if in_kernel and eqn.primitive.name == "dot_general":
                found.append(eqn)
            inner = in_kernel or eqn.primitive.name == "pallas_call"
            for param in eqn.params.values():
                visit(param, inner)

    visit(jaxpr, False)
    return found


@pytest.mark.parametrize("fused", [True, False])
def test_kernel_contractions_are_single_bf16_passes(fused):
    """Every contraction in the kernel body takes bf16 operands at default
    precision into f32: one MXU pass each, d gathers and d histograms (and
    the cube window in the fused kernel).  A multi-pass f32 contraction
    (Precision.HIGHEST, or f32 operands) fails here on the CPU."""
    d = 2
    closed, _, _ = _fill_jaxpr(fused=fused, d=d)
    dots = _dot_generals(closed.jaxpr)
    assert len(dots) == 2 * d + (1 if fused else 0), len(dots)
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.params["precision"] in (
            None, (jax.lax.Precision.DEFAULT,) * 2), eqn.params["precision"]
        assert eqn.params["preferred_element_type"] == jnp.float32


# ---------------------------------------------------------------------------
# interpret autodetect + tile autotune
# ---------------------------------------------------------------------------

def test_backend_default_and_resolve_on_cpu(caplog):
    from repro.engine import backends
    assert jax.default_backend() == "cpu"
    assert backends.backend_default() == "ref"  # not a TPU -> ref
    K._announce.cache_clear()
    with caplog.at_level("INFO", logger="repro.kernels"):
        assert K.resolve_interpret(None) is True
        assert K.resolve_interpret(True) is True
        assert K.resolve_interpret(False) is False  # honored but warned
    text = caplog.text
    assert "INTERPRET on platform=cpu" in text
    assert "autodetected" in text
    assert "only supported on TPU" in text  # the loud explicit-False warning
    K._announce.cache_clear()


def test_config_interpret_none_runs_end_to_end():
    """VegasConfig's default interpret=None autodetects and completes a tiny
    fused pallas run on CPU."""
    from repro.core import VegasConfig, run
    from repro.core import integrands as igs
    ig = igs.make_cosine(dim=2)
    r = run(ig, VegasConfig(neval=4_000, max_it=3, ninc=16, chunk=2048,
                            backend="pallas"),
            key=jax.random.PRNGKey(0))
    assert np.isfinite(r.mean) and r.n_it == 3


@pytest.mark.parametrize("chunk,d,ninc", [
    (16_384, 4, 1024), (2048, 2, 32), (100, 3, 50), (16_384, 16, 1024),
])
def test_autotune_tile_divides_and_fits(chunk, d, ninc):
    t = kops.autotune_tile(chunk, d, ninc, n_cubes=4096)
    assert chunk % t == 0 and 1 <= t <= 1024
    span = vk.span_for_tile(t)
    assert 4 * (d * t * ninc + t * span + 8 * t * d + 3 * d * ninc) <= 8 << 20


def test_fused_rejects_non_f32():
    from repro.core import map as vmap_
    from repro.core import strat
    edges = vmap_.uniform_edges([0.0, 0.0], [1.0, 1.0], 16)
    n_h = strat.uniform_nh(512, 9)
    with pytest.raises(ValueError, match="f32-only"):
        kops.fill(edges, n_h, jax.random.PRNGKey(0), _ig, nstrat=3,
                  n_cap=512, chunk=512, dtype=jnp.float16, fused_cubes=True)


def test_eval_row_bytes_charges_integrand_intermediates():
    """Ridge's (tile, 1000, d) peak distances cost 1000 x 128 lanes x 4 B a
    row as Mosaic pads them, and shrink the autotuned tile; a plain
    (tile, d) integrand costs one padded row."""
    from repro.core import integrands as igs
    ridge = igs.make_ridge(dim=4, n_peaks=1000)
    assert kops.eval_row_bytes(ridge, 4) == 1000 * 128 * 4
    assert kops.eval_row_bytes(igs.make_gaussian(dim=4), 4) == 128 * 4
    kw = dict(chunk=16_384, d=4, ninc=1024, n_cubes=22**4)
    with_rows = kops.autotune_tile(**kw, row_bytes=1000 * 128 * 4)
    assert with_rows < kops.autotune_tile(**kw)
    assert kops.tile_footprint_bytes(with_rows, 4, 1024, 22**4,
                                     row_bytes=1000 * 128 * 4) <= 8 << 20
