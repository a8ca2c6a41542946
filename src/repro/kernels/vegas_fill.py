"""Pallas TPU kernels for the VEGAS+ fill phase (cuVegas' ``vegasFill``).

Two kernels, one contract (DESIGN.md §7, perf iterations P-V1 -> P-V3):

``vegas_fill`` (baseline, P-V2): fuses, per VMEM tile of evaluations,
  stratified-sample decode -> map transform + Jacobian -> integrand eval
  -> importance-map weight accumulation,
with uniforms streamed IN from HBM and per-eval weights streamed OUT (the
per-cube reduction runs as an XLA segment-sum outside the kernel).

``vegas_fill_fused`` (P-V3): the fully streaming kernel.  Uniforms are
generated INSIDE the kernel (bit-exact threefry, matching
``jax.random.uniform(fold_in(key, g), (chunk, d))`` — see ``chunk_uniforms``)
and the per-cube first/second moments are accumulated into a VMEM-resident
accumulator across the sequential grid, so the only per-eval HBM traffic left
is the (chunk, 1) int32 sorted cube-id input: kernel output size is
O(d*ninc + n_cubes) regardless of how many evaluations stream through.

TPU adaptation of the CUDA design (DESIGN.md D1-D4):
  * cuVegas' per-thread ``atomicAdd`` into the (d, ninc) map histogram becomes
    a one-hot matmul on the MXU: ``onehot(iy_k)^T @ w2`` per dimension.  The
    Pallas grid is sequential on TPU, so ``ms_ref[...] +=`` across tiles is
    race-free by construction — no atomics exist and none are needed.
  * The same one-hot matrix implements the edge/width *gathers* (table
    lookups as (tile, ninc) @ (ninc, 6) matvecs) — random HBM access in the
    CUDA kernel becomes dense VMEM-resident MXU work.
  * Every contraction is ONE bf16 MXU pass and exact (``_onehot_dot``): the
    one-hot is 0/1, exact in bf16, and each f32 operand goes in as three
    bf16 pieces (``_split3``, 8 + 8 + 8 significand bits) added back in f32
    as ``(hi + mid) + lo``.  A gather returns the table entry bit for bit;
    a histogram or cube moment is an f32 sum of exact products.
  * The Jacobian is accumulated in log space (overflow-safe for adapted
    high-d maps).
  * The integrand is a traced JAX callable inlined into the kernel body — the
    JAX analogue of cuVegas' Numba-compiled PTX device function.

Block layout per grid step i (grid = n // tile):
  u      (tile, d)   VMEM   uniforms for this tile
  cube   (tile, 1)   VMEM   int32 hypercube ids (n_cubes == masked)
  table  (6d, ninc)  VMEM   bf16 pieces of the left interval edges and
                            widths (``_split_table``; replicated across steps)
  w      (tile, 1)   VMEM   per-eval J*f output
  ms/mc  (d, ninc)   VMEM   accumulated across the sequential grid
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_TINY = 1e-30
_HI16 = 0xFFFF0000


def _split3(t):
    """``t`` (f32) as three f32 pieces, each exact in bf16, whose sum
    ``(hi + mid) + lo`` is ``t`` bit for bit: the top 8 significand bits,
    the next 8 and the last 8.  Each cut masks the low 16 bits off (a
    truncation), so both subtractions are exact; a bf16 round trip would do
    the same, but a compiler allowed excess precision may fold it away.
    Exact for every ``|t| >= 2**-102`` (about 2e-31), also where subnormal
    results flush to zero, as on the TPU (checked over every f32)."""
    def top8(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(_HI16)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    hi = top8(t)
    r = t - hi
    mid = top8(r)
    return hi, mid, r - mid


def _onehot_dot(lhs, rhs, contract):
    """The kernels' one contraction idiom (DESIGN.md D1/D2): a bf16 one-hot
    against bf16 pieces of f32 values (:func:`_split3`), one DEFAULT-precision
    MXU pass with f32 accumulation.  Both operands are exact in bf16, so every
    product is exact and the sums are f32 sums; the caller adds the pieces
    back as ``(hi + mid) + lo``."""
    return jax.lax.dot_general(
        lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16),
        (contract, ((), ())), preferred_element_type=jnp.float32)


def _split_table(edges_lo, widths):
    """The (6d, ninc) bf16 map table both kernels gather from: rows 6k..6k+5
    are the hi/mid/lo pieces of ``edges_lo[k]`` then of ``widths[k]``.  Split
    once per call, outside the grid."""
    d, ninc = edges_lo.shape
    pieces = jnp.stack([*_split3(edges_lo), *_split3(widths)], axis=1)
    return pieces.reshape(6 * d, ninc).astype(jnp.bfloat16)


def _gather_map(oh, table_ref, k: int):
    """(e_lo, dx), each (tile, 1) f32 and bit-exact, for dimension ``k``:
    one matvec of the (tile, ninc) one-hot against the six table rows."""
    g = _onehot_dot(oh, table_ref[6 * k:6 * k + 6, :], ((1,), (1,)))
    return ((g[:, 0:1] + g[:, 1:2]) + g[:, 2:3],
            (g[:, 3:4] + g[:, 4:5]) + g[:, 5:6])


def _stack(split=(), exact=()):
    """The (tile, 3 * len(split) + len(exact)) bf16 operand of
    :func:`_onehot_sums`: the :func:`_split3` pieces of each ``split``
    column, then the ``exact`` columns (0/1 counts, already exact in bf16)."""
    cols = [p for c in split for p in _split3(c)] + list(exact)
    return jnp.concatenate(cols, axis=1).astype(jnp.bfloat16)


def _onehot_sums(lhs, oh, n_split: int):
    """Per one-hot slot, the sum over the tile of each column behind ``lhs``
    (:func:`_stack`), as (1, n) f32 rows in the same order, from one pass:
    each split column is rebuilt from its pieces' sums as
    ``(hi + mid) + lo``."""
    m = _onehot_dot(lhs, oh, ((0,), (0,)))                      # (cols, n)
    rows = [(m[3 * j:3 * j + 1] + m[3 * j + 1:3 * j + 2])
            + m[3 * j + 2:3 * j + 3] for j in range(n_split)]
    return rows + [m[j:j + 1] for j in range(3 * n_split, m.shape[0])]


def _fill_kernel(u_ref, cube_ref, table_ref, *rest,
                 nstrat: int, n_cubes: int, ninc: int, integrand):
    *const_refs, w_ref, ms_ref, mc_ref = rest
    i = pl.program_id(0)
    u = u_ref[...]                      # (tile, d)
    cube = cube_ref[...]                # (tile, 1) int32
    tile, d = u.shape
    dtype = u.dtype

    valid = cube < n_cubes              # (tile, 1)
    cube_c = jnp.minimum(cube, n_cubes - 1)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, ninc), 1)   # (1, ninc)

    # ---- pass 1: per-dimension transform (gathers as one-hot matvecs) ----
    x_cols = []
    iy_cols = []
    logjac = jnp.zeros((tile, 1), dtype)
    for k in range(d):
        c_k = (cube_c // (nstrat**k)) % nstrat                  # (tile, 1)
        y_k = (c_k.astype(dtype) + u[:, k:k + 1]) / nstrat
        yn = y_k * ninc
        iy_k = jnp.clip(yn.astype(jnp.int32), 0, ninc - 1)      # (tile, 1)
        frac = yn - iy_k.astype(dtype)
        oh = (iy_k == lanes).astype(jnp.bfloat16)               # (tile, ninc)
        e_lo, dx = _gather_map(oh, table_ref, k)                # (tile, 1) x2
        x_cols.append(e_lo + frac * dx)
        iy_cols.append(iy_k)
        logjac = logjac + jnp.log(jnp.maximum(ninc * dx, _TINY))

    x = jnp.concatenate(x_cols, axis=1)                         # (tile, d)
    jac = jnp.exp(logjac)                                       # (tile, 1)

    # ---- integrand evaluation (traced into the kernel; closure consts
    # arrive as trailing refs, see ``_const_transport``) ----
    fx = integrand(x, *[r[...] for r in const_refs])
    fx = fx.reshape(tile, 1).astype(dtype)
    w = jnp.where(valid, jac * fx, jnp.zeros((), dtype))        # (tile, 1)
    w_ref[...] = w
    w2cnt = _stack(split=(w * w,), exact=(valid.astype(dtype),))  # (tile, 4)

    # ---- pass 2: map-histogram accumulation (MXU one-hot contractions) ----
    @pl.when(i == 0)
    def _init():
        ms_ref[...] = jnp.zeros_like(ms_ref)
        mc_ref[...] = jnp.zeros_like(mc_ref)

    for k in range(d):
        oh = (iy_cols[k] == lanes).astype(jnp.bfloat16)         # (tile, ninc)
        ms_k, mc_k = _onehot_sums(w2cnt, oh, 1)
        ms_ref[k:k + 1, :] += ms_k
        mc_ref[k:k + 1, :] += mc_k


def _const_transport(integrand, ig_consts):
    """Closure constants ride into the kernel as (1, size) VMEM inputs.

    Returns ``(kernel_integrand, flat_consts, const_specs)``: the flattened
    arrays, their full-block BlockSpecs, and a wrapper restoring the original
    shapes before calling ``integrand(x, *consts)``.  Empty for closure-free
    integrands — the common fast path.
    """
    ig_consts = tuple(ig_consts)
    shapes = [jnp.shape(c) for c in ig_consts]
    flat = [jnp.reshape(c, (1, max(int(jnp.size(c)), 1))) for c in ig_consts]
    specs = [pl.BlockSpec(f.shape, lambda i: (0, 0)) for f in flat]

    def kernel_integrand(x, *flat_refs):
        return integrand(x, *[f.reshape(s)
                              for f, s in zip(flat_refs, shapes)])

    return kernel_integrand, flat, specs


def vegas_fill(u, cube, edges_lo, widths, *, nstrat: int, n_cubes: int,
               integrand, tile: int = 256, interpret: bool = True,
               ig_consts=()):
    """pallas_call wrapper. Shapes as in kernels/ref.py; ``n % tile == 0``.

    ``ig_consts``: arrays closed over by ``integrand`` (from
    ``jax.closure_convert``), passed through as kernel inputs — the integrand
    is then called as ``integrand(x, *ig_consts)``.
    """
    n, d = u.shape
    ninc = edges_lo.shape[1]
    assert n % tile == 0, (n, tile)
    dtype = u.dtype
    assert dtype == jnp.float32, "the bf16 split is exact for f32 only"
    kig, flat_consts, const_specs = _const_transport(integrand, ig_consts)

    kernel = functools.partial(_fill_kernel, nstrat=nstrat, n_cubes=n_cubes,
                               ninc=ninc, integrand=kig)
    grid = (n // tile,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),      # u
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),      # cube
            pl.BlockSpec((6 * d, ninc), lambda i: (0, 0)),  # split map table
            *const_specs,                                   # integrand consts
        ],
        out_specs=[
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),      # w
            pl.BlockSpec((d, ninc), lambda i: (0, 0)),      # map sums
            pl.BlockSpec((d, ninc), lambda i: (0, 0)),      # map counts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), dtype),
            jax.ShapeDtypeStruct((d, ninc), dtype),
            jax.ShapeDtypeStruct((d, ninc), dtype),
        ],
        interpret=interpret,
        name="vegas_fill",
    )(u, cube, _split_table(edges_lo, widths), *flat_consts)


# ---------------------------------------------------------------------------
# In-kernel RNG (P-V3 part 1): threefry-2x32 counter mode, bit-exact with
# jax.random.uniform under the default (non-partitionable) threefry impl.
# ---------------------------------------------------------------------------

LANE = 128          # TPU lane width: cube-accumulator rows/offsets align to it
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def _rotl32(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32(k0, k1, x0, x1):
    """The Threefry-2x32 block cipher on uint32 arrays, written in plain jnp
    ops (shifts/xor/add) so it traces into a Pallas kernel body — same key
    schedule and rotation constants as jax._src.prng.threefry2x32_p, so the
    outputs are bit-identical to what ``jax.random`` produces."""
    k2 = k0 ^ k1 ^ jnp.uint32(0x1BD11BDA)
    x0 = x0 + k0
    x1 = x1 + k1
    sched = ((k1, k2), (k2, k0), (k0, k1), (k1, k2), (k2, k0))
    rots = (_ROT_A, _ROT_B, _ROT_A, _ROT_B, _ROT_A)
    for i in range(5):
        for r in rots[i]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x0 ^ x1
        a, b = sched[i]
        x0 = x0 + a
        x1 = x1 + b + jnp.uint32(i + 1)
    return x0, x1


def _partitionable() -> bool:
    """The jax_threefry_partitionable flag, read at TRACE time: it selects
    which of jax's two threefry counter layouts the in-kernel RNG must
    reproduce (flipping the flag between trace and execution is not
    supported — neither is it for jax.random itself under jit)."""
    return bool(jax.config.jax_threefry_partitionable)


def _uniform_from_counts(k0, k1, c, n_total: int):
    """f32 uniforms in [0, 1) for flat counter positions ``c`` (uint32) of a
    ``jax.random.uniform(key, shape)`` draw with ``prod(shape) == n_total``.

    Matches jax's threefry counter layout bit-for-bit under BOTH settings of
    ``jax_threefry_partitionable``:
      * partitionable: element ``c`` is ``xor(threefry(key, hi32(c),
        lo32(c)))`` — purely per-element (requires ``n_total < 2**32``, which
        a chunk always satisfies);
      * original: ``iota(n_total)`` is split into two halves (the odd case
        pads one zero) fed as the two cipher inputs, so element ``c`` lives
        in block ``c mod half`` and takes cipher output 0 or 1 by half.
    The float conversion mirrors ``jax._src.random._uniform``: randomize the
    mantissa at exponent 0 and subtract 1.
    """
    if _partitionable():
        o0, o1 = _threefry2x32(k0, k1, jnp.zeros_like(c), c)
        bits = o0 ^ o1
    else:
        half = (n_total + 1) // 2
        in_lo = c < jnp.uint32(half)
        b = jnp.where(in_lo, c, c - jnp.uint32(half))
        hi = b + jnp.uint32(half)
        if n_total % 2:
            hi = jnp.where(hi == jnp.uint32(n_total), jnp.uint32(0), hi)
        o0, o1 = _threefry2x32(k0, k1, b, hi)
        bits = jnp.where(in_lo, o0, o1)
    fb = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    u = jax.lax.bitcast_convert_type(fb, jnp.float32) - jnp.float32(1.0)
    return jnp.maximum(u, jnp.float32(0.0))


def _tile_uniforms(k0, k1, row0, tile: int, chunk: int, d: int):
    """(tile, d) uniforms == rows [row0, row0+tile) of
    ``jax.random.uniform(key, (chunk, d))`` for the key behind (k0, k1)."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, (tile, d), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (tile, d), 1)
    c = (jnp.uint32(row0) + rows) * jnp.uint32(d) + cols
    return _uniform_from_counts(k0, k1, c, chunk * d)


def chunk_uniforms(key_bits, *, chunk: int, d: int, tile: int | None = None):
    """Reassemble a whole chunk's uniforms from per-tile in-kernel draws.

    ``key_bits``: (2,) uint32 raw key data of ``fold_in(key, g)``.  Equals
    ``jax.random.uniform(fold_in(key, g), (chunk, d))`` BIT-FOR-BIT (the RNG
    contract test); ``tile`` exercises the same slicing the kernel grid uses.
    """
    tile = chunk if tile is None else tile
    assert chunk % tile == 0, (chunk, tile)
    k0, k1 = key_bits[0], key_bits[1]
    parts = [_tile_uniforms(k0, k1, i * tile, tile, chunk, d)
             for i in range(chunk // tile)]
    return jnp.concatenate(parts, axis=0)


def span_for_tile(tile: int) -> int:
    """Width of the per-tile cube-id window: sorted ids advance by at most one
    per eval, so a tile touches <= tile distinct ids; aligning the window base
    down to a LANE boundary costs at most LANE - 1 extra slots."""
    return ((tile + LANE - 1) // LANE) * LANE + LANE


def padded_cube_rows(n_cubes: int, tile: int) -> int:
    """Rows of the (rows, LANE) VMEM cube accumulator: the highest window base
    is align_down(n_cubes - 1), and the window extends span slots past it."""
    return (max(n_cubes - 1, 0) // LANE) + span_for_tile(tile) // LANE


# ---------------------------------------------------------------------------
# P-V3 fused kernel: in-kernel RNG + in-kernel cube accumulation
# ---------------------------------------------------------------------------

def _fill_fused_kernel(*refs, nstrat: int, n_cubes: int, ninc: int,
                       chunk: int, tile: int, d: int, integrand,
                       rng_in_kernel: bool, accum_dtype=jnp.float32):
    (rng_or_u_ref, cube_ref, table_ref, *const_refs,
     ms_ref, mc_ref, s1_ref, s2_ref) = refs
    if rng_in_kernel:
        kd_ref = rng_or_u_ref
    else:
        u_ref = rng_or_u_ref
    i = pl.program_id(0)
    dtype = jnp.float32
    cube = cube_ref[...]                        # (tile, 1) int32, sorted

    if rng_in_kernel:
        # ---- in-kernel RNG: this tile's slice of uniform(fold_in(key, g)),
        # bit-exact (P-V3 part 1; zero per-eval input traffic) ----
        u = _tile_uniforms(kd_ref[0, 0], kd_ref[0, 1], i * tile, tile,
                           chunk, d)                            # (tile, d)
    else:
        u = u_ref[...]                                          # (tile, d)

    valid = cube < n_cubes                      # (tile, 1)
    cube_c = jnp.minimum(cube, n_cubes - 1)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, ninc), 1)   # (1, ninc)

    # ---- pass 1: per-dimension transform.  One gather matvec per
    # dimension picks (e_lo, dx) together from the split table. ----
    x_cols = []
    ohs = []                                    # kept live for pass 2 reuse
    logjac = jnp.zeros((tile, 1), dtype)
    for k in range(d):
        c_k = (cube_c // (nstrat**k)) % nstrat                  # (tile, 1)
        y_k = (c_k.astype(dtype) + u[:, k:k + 1]) / nstrat
        yn = y_k * ninc
        iy_k = jnp.clip(yn.astype(jnp.int32), 0, ninc - 1)      # (tile, 1)
        frac = yn - iy_k.astype(dtype)
        oh = (iy_k == lanes).astype(jnp.bfloat16)               # (tile, ninc)
        e_lo, dx = _gather_map(oh, table_ref, k)                # (tile, 1) x2
        x_cols.append(e_lo + frac * dx)
        ohs.append(oh)
        logjac = logjac + jnp.log(jnp.maximum(ninc * dx, _TINY))

    x = jnp.concatenate(x_cols, axis=1)                         # (tile, d)
    jac = jnp.exp(logjac)                                       # (tile, 1)

    # ---- integrand evaluation (traced into the kernel; closure consts
    # arrive as trailing refs, see ``_const_transport``) ----
    fx = integrand(x, *[r[...] for r in const_refs])
    fx = fx.reshape(tile, 1).astype(dtype)
    w = jnp.where(valid, jac * fx, jnp.zeros((), dtype))        # (tile, 1)
    w2 = w * w

    @pl.when(i == 0)
    def _init():
        ms_ref[...] = jnp.zeros_like(ms_ref)
        mc_ref[...] = jnp.zeros_like(mc_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # ---- pass 2: map histogram.  REUSES the pass-1 one-hots (no second
    # construction) and contracts [w2 pieces, cnt] in ONE matmul per dim.
    # Products are exact and summed in f32 on the MXU; the §15 widening
    # happens on the per-tile partial, just before the running sum into the
    # (possibly f64) VMEM accumulator ref. ----
    accum = jnp.dtype(accum_dtype)
    w2cnt = _stack(split=(w2,), exact=(valid.astype(dtype),))   # (tile, 4)
    for k in range(d):
        ms_k, mc_k = _onehot_sums(w2cnt, ohs[k], 1)
        ms_ref[k:k + 1, :] += ms_k.astype(accum)
        mc_ref[k:k + 1, :] += mc_k.astype(accum)

    # ---- fused cube accumulation (P-V3 part 2) ----
    # Sorted ids advance by <= 1 per eval (every cube draws >= 2 evals), so
    # this tile's live ids fit a contiguous window of `span` slots starting at
    # a LANE-aligned base below the first id.  One-hot against the WINDOW
    # (tile x span, tiny) instead of all n_cubes; masked overflow evals are
    # clipped into the window but contribute exactly 0.
    span = span_for_tile(tile)
    base = (cube_c[0, 0] // LANE) * LANE                        # scalar
    rel = jnp.clip(cube_c - base, 0, span - 1)                  # (tile, 1)
    win = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    ohc = (rel == win).astype(jnp.bfloat16)                     # (tile, span)
    s1, s2 = _onehot_sums(_stack(split=(w, w2)), ohc, 2)       # (1, span) x2
    br = base // LANE
    # Same §15 boundary as the map histogram: the one-hot contraction stays
    # f32, each tile's partial is widened once before the grid-sequential +=
    # into the accumulator tiles.  The (1, span) -> (rows, LANE) fold runs
    # as one lane-aligned slice per accumulator row: Mosaic has no layout
    # for reshaping a lane-major row vector into sublanes.
    for r in range(span // LANE):
        lanes_r = slice(r * LANE, (r + 1) * LANE)
        s1_ref[pl.ds(br + r, 1), :] += s1[:, lanes_r].astype(accum)
        s2_ref[pl.ds(br + r, 1), :] += s2[:, lanes_r].astype(accum)


def vegas_fill_fused(key_bits, cube, edges_lo, widths, *, nstrat: int,
                     n_cubes: int, integrand, tile: int = 256,
                     interpret: bool = True, u=None, ig_consts=(),
                     accum_dtype=None):
    """pallas_call wrapper for the P-V3 streaming kernel (one chunk).

    Args:
      key_bits: (1, 2) uint32 raw key data of ``fold_in(key, gchunk)``.
      cube:     (chunk, 1) int32 SORTED cube ids; ``n_cubes`` == masked.
      edges_lo/widths: (d, ninc) f32 map tables.
      accum_dtype: accumulator dtype (default f32).  Under the §15 widened
                policy the four output buffers — and the VMEM accumulator
                tiles behind them — are f64 while every product (transform,
                integrand) stays f32 and the one-hot matmuls sum in f32 on
                the MXU; each tile's partial is widened once before the
                running ``+=``.
      u:        optional (chunk, d) f32 uniforms.  ``None`` (the compiled-TPU
                default) generates them IN-KERNEL from ``key_bits`` — zero
                per-eval input traffic.  Passing the precomputed chunk block
                keeps the rest of the fusion but streams uniforms from HBM:
                the interpret-mode escape hatch (XLA:CPU refuses to vectorize
                fusion clusters polluted by the in-body threefry, a ~2x
                pessimization measured in DESIGN.md §7 — irrelevant on real
                TPU where Mosaic compiles the u32 rotate/xor chain natively).

    Returns ``(ms, mc, s1_pad, s2_pad)`` where the cube moments come back as
    (rows, LANE) f32 — flatten and trim to ``n_cubes``.  No per-eval output
    exists: with in-kernel RNG the only per-eval HBM traffic is the int32
    cube-id input; kernel output is O(d*ninc + n_cubes) state.
    """
    chunk = cube.shape[0]
    d, ninc = edges_lo.shape
    assert chunk % tile == 0, (chunk, tile)
    assert edges_lo.dtype == jnp.float32, "fused path is f32-only (RNG contract)"
    accum = jnp.dtype(accum_dtype) if accum_dtype is not None else jnp.float32
    rows = padded_cube_rows(n_cubes, tile)
    rng_in_kernel = u is None
    kig, flat_consts, const_specs = _const_transport(integrand, ig_consts)

    kernel = functools.partial(
        _fill_fused_kernel, nstrat=nstrat, n_cubes=n_cubes, ninc=ninc,
        chunk=chunk, tile=tile, d=d, integrand=kig,
        rng_in_kernel=rng_in_kernel, accum_dtype=accum)
    grid = (chunk // tile,)
    first_in = (key_bits, pl.BlockSpec((1, 2), lambda i: (0, 0))) \
        if rng_in_kernel else (u, pl.BlockSpec((tile, d), lambda i: (i, 0)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            first_in[1],                                    # key bits | u
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),      # cube ids
            pl.BlockSpec((6 * d, ninc), lambda i: (0, 0)),  # split map table
            *const_specs,                                   # integrand consts
        ],
        out_specs=[
            pl.BlockSpec((d, ninc), lambda i: (0, 0)),      # map sums
            pl.BlockSpec((d, ninc), lambda i: (0, 0)),      # map counts
            pl.BlockSpec((rows, LANE), lambda i: (0, 0)),   # cube s1
            pl.BlockSpec((rows, LANE), lambda i: (0, 0)),   # cube s2
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, ninc), accum),
            jax.ShapeDtypeStruct((d, ninc), accum),
            jax.ShapeDtypeStruct((rows, LANE), accum),
            jax.ShapeDtypeStruct((rows, LANE), accum),
        ],
        interpret=interpret,
        name="vegas_fill_fused",
    )(first_in[0], cube, _split_table(edges_lo, widths), *flat_consts)
