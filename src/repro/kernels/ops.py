"""Jitted wrapper exposing the Pallas fill kernels behind the core FillResult
contract (the 'pallas'/'pallas-fused' entries of the engine's backend
registry, via core.fill.fill_pallas).

The fill is scan-chunked exactly like ``core.fill.fill_reference``: chunk
``g`` draws its uniforms from ``fold_in(key, g)`` and takes the cube ids of
global evals ``[g * chunk, (g + 1) * chunk)`` from the range's ids, built
once per call (4 bytes a lane).  Uniforms and weights live one chunk at a
time, and ``start_chunk``/``n_chunks`` select a contiguous chunk range — the
unit ``dist.sharded_fill`` distributes (DESIGN.md C5).

Two kernel paths (DESIGN.md §7):
  * ``fused_cubes=False`` (P-V2 baseline): uniforms materialized per chunk in
    HBM, per-eval weights streamed back out, cube reduction via XLA
    segment-sum over the sorted ids.
  * ``fused_cubes=True``  (P-V3): the streaming kernel — in-kernel threefry
    RNG (bit-identical streams) + VMEM-resident cube accumulation; no
    per-eval array ever exists, in HBM or as a kernel output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import strat
from . import resolve_interpret
from . import vegas_fill as vk


def hoist_closure(integrand, x_shape, dtype):
    """Split ``integrand`` into a closure-free function + the arrays it
    closes over (ridge's peak table, a batched family's vmapped params, ...).

    A traced pallas kernel body may not capture constants or outer-trace
    tracers, so ops.fill hoists them here and ships them through the kernel
    as explicit inputs.  (``jax.closure_convert`` is not enough: it hoists
    only tracers involved in differentiation, leaving plain array constants
    in the closure.)  Returns ``(pure_fn(x, *consts), consts)``.
    """
    closed = jax.make_jaxpr(lambda xx: integrand(xx))(
        jax.ShapeDtypeStruct(x_shape, dtype))
    consts = tuple(closed.consts)

    def pure(x, *cs):
        out = jax.core.eval_jaxpr(closed.jaxpr, list(cs), x)
        return out[0]

    return pure, consts


def key_bits(key) -> jax.Array:
    """Raw (2,) uint32 key data for either a legacy raw key or a typed key."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


#: Row count the integrand is traced at to size its intermediates: odd, so
#: no other axis of a realistic integrand matches it by accident.
_PROBE_ROWS = 257
_SUBLANE = 8
#: VMEM bytes per element of a one-hot operand: the bf16 one-hot that one
#: MXU pass contracts (vegas_fill._onehot_dot) and Mosaic's scratch around
#: it.  Mosaic's scoped allocation for the fused kernel, compiled for a TPU
#: v5e, grows by 2.7-4.0 bytes per one-hot element across d = 2..16 and
#: tiles 32..256 at ninc 1024 (16.4-17.6 when the contractions ran as
#: six-pass f32 matmuls at Precision.HIGHEST).
_ONEHOT_BYTES = 4


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row_bytes(aval, rows: int) -> int:
    """VMEM bytes per evaluation row of one intermediate whose leading axis
    is the tile's rows, laid out as Mosaic tiles f32: the minor axis padded
    to LANE, the second-minor to 8 sublanes.  0 for anything else."""
    shape = getattr(aval, "shape", ())
    if not shape or shape[0] != rows:
        return 0
    itemsize = aval.dtype.itemsize
    if len(shape) == 1:                  # (rows,): rows ride the lanes
        return itemsize
    if len(shape) == 2:                  # (rows, k): rows ride the sublanes
        return _round_up(shape[1], vk.LANE) * itemsize
    inner = 1
    for n in shape[1:-2]:
        inner *= n
    return (inner * _round_up(shape[-2], _SUBLANE)
            * _round_up(shape[-1], vk.LANE) * itemsize)


def _jaxpr_row_bytes(jaxpr, rows: int) -> int:
    best = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            best = max(best, _row_bytes(v.aval, rows))
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)          # ClosedJaxpr -> Jaxpr
            if hasattr(sub, "eqns"):
                best = max(best, _jaxpr_row_bytes(sub, rows))
    return best


def eval_row_bytes(integrand, d: int, dtype=jnp.float32) -> int:
    """Per-row VMEM bytes of the integrand's largest intermediate, read off
    its jaxpr (ridge's ``(tile, 1000, d)`` peak distances: 512 KB a row).
    Mosaic keeps the integrand's intermediates in VMEM next to the fill's
    own scratch, so the tile budget must charge them.  The largest one
    alone is the charge: Mosaic reuses buffers between the others (it
    allocates ~250 KB a row for ridge, under this 512 KB bound)."""
    closed = jax.make_jaxpr(lambda xx: integrand(xx))(
        jax.ShapeDtypeStruct((_PROBE_ROWS, d), dtype))
    return _jaxpr_row_bytes(closed.jaxpr, _PROBE_ROWS)


def tile_footprint_bytes(tile: int, d: int, ninc: int, n_cubes: int, *,
                         accum_itemsize: int = 4, row_bytes: int = 0) -> int:
    """VMEM footprint of one kernel tile under the DESIGN.md §7/§15 budget
    math: the d pass-1 one-hots stay live for pass-2 reuse (d * tile * ninc
    elements at ``_ONEHOT_BYTES`` — bf16, each contraction one exact MXU
    pass), the cube-window one-hot adds tile * span more, the transform
    scratch ~8 f32 copies of (tile, d), the integrand's own intermediates
    (``row_bytes`` a row, see :func:`eval_row_bytes`), plus the
    grid-resident state — the map table at 8 bytes a (d, ninc) entry (the
    two f32 tables it is split from; the allocation ``_ONEHOT_BYTES`` was
    fit to includes its six bf16 pieces) and the ACCUMULATORS at
    ``accum_itemsize`` bytes
    apiece: the (d, ninc) ms/mc histogram pair and the two (rows, LANE)
    cube-moment tiles (~2.1 MB f32 / ~4.2 MB f64 at the max_cubes = 2^18
    cap).  Widened f64 accumulation therefore shrinks the budget available
    to per-tile scratch — the §15 VMEM tradeoff `valid_tiles` prices."""
    span = vk.span_for_tile(tile)
    resident = (4 * 2 * d * ninc
                + accum_itemsize * (2 * d * ninc
                                    + 2 * vk.padded_cube_rows(n_cubes, tile)
                                    * vk.LANE))
    return (_ONEHOT_BYTES * (d * tile * ninc + tile * span)
            + 4 * 8 * tile * d + tile * row_bytes + resident)


def valid_tiles(chunk: int, d: int, ninc: int, n_cubes: int, *,
                vmem_budget: int = 8 << 20, max_tile: int = 1024,
                accum_itemsize: int = 4, row_bytes: int = 0) -> list[int]:
    """Every tile the kernel accepts for this shape, ascending: divisors of
    ``chunk`` whose :func:`tile_footprint_bytes` fits the VMEM budget.

    This is the single validity oracle shared by :func:`autotune_tile` (which
    takes the largest entry) and the plan autotuner (`engine.autotune`, which
    scores entries with the measured cost model) — so the autotuner can never
    choose a tile the kernel would reject.  ``accum_itemsize`` prices the
    grid-resident accumulators (8 under an f64 PrecisionPolicy, §15);
    ``row_bytes`` the integrand's intermediates (:func:`eval_row_bytes`).
    """
    return [t for t in range(1, min(chunk, max_tile) + 1)
            if chunk % t == 0
            and tile_footprint_bytes(t, d, ninc, n_cubes,
                                     accum_itemsize=accum_itemsize,
                                     row_bytes=row_bytes)
            <= vmem_budget]


def autotune_tile(chunk: int, d: int, ninc: int, n_cubes: int, *,
                  vmem_budget: int = 8 << 20, max_tile: int = 1024,
                  accum_itemsize: int = 4, row_bytes: int = 0) -> int:
    """Largest tile that divides ``chunk`` and fits the VMEM budget (the
    static default when no measured cost table picks one)."""
    tiles = valid_tiles(chunk, d, ninc, n_cubes, vmem_budget=vmem_budget,
                        max_tile=max_tile, accum_itemsize=accum_itemsize,
                        row_bytes=row_bytes)
    return tiles[-1] if tiles else 1


def _pick_tile(tile: int | None, chunk: int, d: int, ninc: int,
               n_cubes: int, accum_itemsize: int = 4,
               row_bytes: int = 0) -> int:
    if tile is None:
        tile = autotune_tile(chunk, d, ninc, n_cubes,
                             accum_itemsize=accum_itemsize,
                             row_bytes=row_bytes)
    else:
        tile = min(tile, chunk)
        if chunk % tile != 0:
            # The scanned grid is per-chunk, so the tile must divide chunk:
            # fall back to the largest divisor below the request.
            tile = next(t for t in range(tile, 0, -1) if chunk % t == 0)
    if tile < min(8, chunk):
        # e.g. a prime chunk: the only divisor is 1, which would explode the
        # sequential grid (catastrophic under interpret mode).
        raise ValueError(
            f"chunk={chunk} has no usable tile divisor <= {tile}; "
            f"pick a chunk with a divisor >= 8 (or a tile dividing it)")
    return tile


def fill(edges, n_h, key, integrand, *, nstrat: int, n_cap: int, chunk: int,
         dtype=jnp.float32, accum_dtype=None, interpret: bool | None = None,
         fused_cubes: bool = True, tile: int | None = None, start_chunk=0,
         n_chunks: int | None = None, kahan: bool = False,
         return_comp: bool = False, rng_in_kernel: bool | None = None):
    """Kernel-backed fill pass returning core.fill.FillResult.

    RNG follows the same global-chunk contract as core.fill.fill_reference:
    uniforms for global chunk g are uniform(fold_in(key, g)) — bit-identical
    streams across backends and elastic across any device count.  ``kahan``
    carries a compensation term through the chunk scan (device-count
    invariance, DESIGN.md §5).

    ``rng_in_kernel=None`` resolves to ``not interpret``: the streaming
    kernel generates its own uniforms when compiled for TPU (zero per-eval
    float traffic), while the interpreter gets them precomputed per chunk —
    bit-identical either way, see ``vegas_fill.vegas_fill_fused``.

    ``accum_dtype`` (default: ``dtype``) widens every moment accumulator
    (§15): products and the MXU's sums stay f32, but the fused kernel's VMEM
    accumulator tiles — and the baseline path's XLA scatter-adds — carry the
    wider dtype, and the returned FillResult comes back in it.
    ``return_comp=True`` (with ``kahan=True``) returns the (sums,
    compensation) pair for the shard-boundary psum — see
    ``core.fill.fill_reference``.
    """
    from repro.core.fill import FillResult

    if return_comp and not kahan:
        raise ValueError("return_comp=True requires kahan=True (there is "
                         "no compensation term to return)")
    interpret = resolve_interpret(interpret)
    if rng_in_kernel is None:
        rng_in_kernel = not interpret
    dtype = jnp.dtype(dtype)
    accum = jnp.dtype(accum_dtype) if accum_dtype is not None else dtype
    d = edges.shape[0]
    ninc = edges.shape[1] - 1
    n_cubes = n_h.shape[0]
    if n_chunks is None:
        assert n_cap % chunk == 0, (n_cap, chunk)
        n_chunks = n_cap // chunk
    tile = _pick_tile(tile, chunk, d, ninc, n_cubes, accum.itemsize,
                      eval_row_bytes(integrand, d, dtype) if tile is None
                      else 0)
    if dtype != jnp.float32:
        raise ValueError(
            f"the fill kernels are f32-only samples (the in-kernel RNG "
            f"reproduces the f32 uniform bit pattern, and the one-hot "
            f"contractions split f32 exactly into bf16 pieces; widen "
            f"accum_dtype instead, §15); got dtype={dtype}")
    if accum not in (jnp.float32, jnp.float64):
        raise ValueError(f"accum_dtype must be float32 or float64, "
                         f"got {accum}")

    edges_lo = edges[:, :-1].astype(dtype)
    widths = jnp.diff(edges, axis=1).astype(dtype)
    pure_ig, ig_consts = hoist_closure(integrand, (tile, d), dtype)

    def chunk_contrib(gchunk, cube):
        k = jax.random.fold_in(key, gchunk)
        if fused_cubes:
            u = (None if rng_in_kernel else
                 jax.random.uniform(k, (chunk, d), dtype=dtype))
            ms, mc, s1p, s2p = vk.vegas_fill_fused(
                key_bits(k).reshape(1, 2), cube.reshape(chunk, 1), edges_lo,
                widths, nstrat=nstrat, n_cubes=n_cubes, integrand=pure_ig,
                tile=tile, interpret=interpret, u=u, ig_consts=ig_consts,
                accum_dtype=accum)
            return FillResult(ms, mc, s1p.reshape(-1)[:n_cubes],
                              s2p.reshape(-1)[:n_cubes])
        u = jax.random.uniform(k, (chunk, d), dtype=dtype)
        w, ms, mc = vk.vegas_fill(u, cube.reshape(chunk, 1), edges_lo, widths,
                                  nstrat=nstrat, n_cubes=n_cubes,
                                  integrand=pure_ig, tile=tile,
                                  interpret=interpret, ig_consts=ig_consts)
        # The baseline kernel streams per-eval weights and per-chunk f32 map
        # partials; the §15 widening happens at the accumulation boundary —
        # the scatter below and the cross-chunk scan run in ``accum``.
        w = w.reshape(chunk).astype(accum)
        # Per-cube reduction outside the kernel (ids are sorted; XLA lowers
        # this to a sorted-scatter; the overflow bucket is dropped).
        s1 = jnp.zeros((n_cubes + 1,), accum).at[cube].add(w)[:n_cubes]
        s2 = jnp.zeros((n_cubes + 1,), accum).at[cube].add(w * w)[:n_cubes]
        return FillResult(ms.astype(accum), mc.astype(accum), s1, s2)

    def body(carry, xs):
        step, cube = xs
        contrib = chunk_contrib(start_chunk + step, cube)
        if not kahan:
            return carry + contrib, None
        acc, comp = carry
        y = jax.tree.map(jnp.subtract, contrib, comp)
        t = jax.tree.map(jnp.add, acc, y)
        comp = jax.tree.map(lambda tt, a, yy: (tt - a) - yy, t, acc, y)
        return (t, comp), None

    zero = FillResult(jnp.zeros((d, ninc), accum), jnp.zeros((d, ninc), accum),
                      jnp.zeros((n_cubes,), accum), jnp.zeros((n_cubes,), accum))
    init = (zero, zero) if kahan else zero
    cubes = strat.cubes_for_slice(n_h, start_chunk * chunk, n_chunks * chunk)
    out, _ = jax.lax.scan(body, init, (jnp.arange(n_chunks),
                                       cubes.reshape(n_chunks, chunk)))
    if kahan:
        return out if return_comp else out[0]
    return out
