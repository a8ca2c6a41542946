"""Pallas GPU (Triton-lowered) kernel for the VEGAS+ fill phase — the
``pallas-gpu`` backend of the engine registry (DESIGN.md §14).

Same contract as ``vegas_fill.vegas_fill_fused`` (the TPU/Mosaic P-V3
kernel), restructured for how a CUDA-class device actually wants the work
(m-Cubes arXiv:2202.01753 / PAGANI arXiv:2104.06494 — GPU integrators live
or die by how per-cube accumulation maps onto the memory hierarchy):

  * **grid over sample blocks, programs in PARALLEL** — the Mosaic grid is
    sequential, so the TPU kernel initializes its accumulators under
    ``@pl.when(i == 0)`` and accumulates with plain ``ref[...] +=``.  Triton
    programs race on both, so outputs here are **zero-initialized inputs
    aliased to outputs** (``input_output_aliases``) and every cross-program
    accumulation is a ``pl.atomic_add`` — cuVegas' own design (its D1
    deviation point: the CUDA kernel leans on atomics; the TPU port removed
    them, this backend puts them back where the hardware supports them).
  * **block-privatized histograms** — the canonical CUDA histogram idiom:
    each program reduces its ``block`` evaluations into a private partial
    histogram (a masked sum per bucket, held in registers/shared memory) and
    flushes ONE atomic add per bucket at in-call-unique indices.  Duplicate
    bucket hits therefore only ever collide ACROSS programs, where the
    atomics serialize them — never within one vectorized atomic call (whose
    semantics for duplicate indices are undefined-order, and which the
    interpreter resolves as last-write-wins).
  * **scatter/segment-sum cube accumulation** — the sorted cube ids advance
    by at most one per eval (every cube draws >= 2), so a block's ids span a
    window of <= ``block`` distinct slots starting at its first id; the
    per-window partial moments flush with one atomic add per slot into a
    flat ``(n_cubes + block,)`` accumulator (trimmed by the wrapper).  The
    TPU kernel's LANE-aligned one-hot *matmul* into a (rows, 128) VMEM
    accumulator only makes sense feeding an MXU — on GPU it would burn
    Tensor-Core shapes on what is fundamentally a scatter.
  * **gather loads, not one-hot matvecs** — map-table lookups are pointer
    gathers (``ew_ref[0, k*ninc + iy]``), the thing a GPU memory system is
    built for; the MXU gather-as-matmul trick is dropped.
  * **in-kernel threefry-2x32** — byte-identical to the TPU kernel's
    (``vegas_fill._tile_uniforms``): uniforms for global chunk ``g`` match
    ``jax.random.uniform(fold_in(key, g), (chunk, d))`` bit-for-bit under
    BOTH ``jax_threefry_partitionable`` layouts, so the existing parity and
    RNG-contract suites apply to this backend verbatim.

Knobs (declared in ``engine.backends`` like ``tile`` is for the TPU path):
``block`` — evaluations per program, the CUDA block-size analogue, default
from :func:`autotune_block` (largest power-of-two divisor of ``chunk``
within the shared-memory budget model); ``num_warps`` — forwarded to the
Triton compiler (``TritonCompilerParams``), harmless under interpret mode.

CI validates this kernel in interpret mode on CPU (the Pallas interpreter
runs the grid sequentially — atomics degenerate to plain adds, results are
deterministic); on a real GPU the compiled kernel's float atomics make
cube/map sums run-to-run nondeterministic at reduction-order level, the
same tradeoff cuVegas ships with (DESIGN.md §14).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import strat
from . import resolve_interpret
from . import vegas_fill as vk

_TINY = 1e-30

#: Shared-memory budget model for the ``block`` knob (bytes per program).
#: Ampere/Hopper parts carry 100-228 KB of shared memory per SM; 192 KB is
#: the documented planning budget (DESIGN.md §14) — generous enough that the
#: model constrains only genuinely oversized blocks, conservative enough
#: that one program's privatized histograms never spill to local memory.
SMEM_BUDGET = 192 << 10


def block_footprint_bytes(block: int, d: int, ninc: int, *,
                          accum_itemsize: int = 4) -> int:
    """Per-program scratch under the DESIGN.md §14/§15 budget math: the
    (block, ninc) masked partial behind the private map histogram and the
    (block, block) cube-window partial materialize at ``accum_itemsize``
    bytes (8 under a widened f64 policy — the where-products are widened
    BEFORE the masked sums so the privatized partials genuinely carry the
    accumulation dtype), plus ~8 f32 (block, d) transform temporaries.
    No grid-resident term: unlike the TPU kernel's VMEM accumulators, the
    full-size accumulators live in HBM behind atomics."""
    return (accum_itemsize * (block * ninc + block * block)
            + 4 * 8 * block * d)


def valid_blocks(chunk: int, d: int, ninc: int, *,
                 budget: int = SMEM_BUDGET,
                 max_block: int = 1024, accum_itemsize: int = 4) -> list[int]:
    """Every block size the kernel accepts for this shape, ascending:
    divisors of ``chunk`` whose :func:`block_footprint_bytes` fits the
    budget.  The single validity oracle shared by :func:`autotune_block` and
    the plan autotuner (`engine.autotune`) — mirroring ``ops.valid_tiles``
    so the tuner can never choose a block the kernel would reject.
    ``accum_itemsize`` prices the privatized partials (8 under an f64
    PrecisionPolicy, §15)."""
    return [b for b in range(1, min(chunk, max_block) + 1)
            if chunk % b == 0
            and block_footprint_bytes(b, d, ninc,
                                      accum_itemsize=accum_itemsize)
            <= budget]


def autotune_block(chunk: int, d: int, ninc: int, *,
                   budget: int = SMEM_BUDGET, max_block: int = 1024,
                   accum_itemsize: int = 4) -> int:
    """Largest power-of-two valid block (Triton tiles powers of two well;
    any valid divisor is accepted when no power of two fits)."""
    blocks = valid_blocks(chunk, d, ninc, budget=budget, max_block=max_block,
                          accum_itemsize=accum_itemsize)
    pow2 = [b for b in blocks if (b & (b - 1)) == 0]
    return (pow2 or blocks or [1])[-1]


def _pick_block(block: int | None, chunk: int, d: int, ninc: int,
                accum_itemsize: int = 4) -> int:
    if block is None:
        block = autotune_block(chunk, d, ninc,
                               accum_itemsize=accum_itemsize)
    else:
        block = min(block, chunk)
        if chunk % block != 0:
            # The grid is per-chunk, so the block must divide chunk: fall
            # back to the largest divisor below the request (same rule as
            # the TPU path's _pick_tile).
            block = next(b for b in range(block, 0, -1) if chunk % b == 0)
    if block < min(8, chunk):
        raise ValueError(
            f"chunk={chunk} has no usable block divisor <= {block}; "
            f"pick a chunk with a divisor >= 8 (or a block dividing it)")
    return block


def _fill_gpu_kernel(*refs, nstrat: int, n_cubes: int, ninc: int, chunk: int,
                     block: int, d: int, integrand, rng_in_kernel: bool,
                     accum_dtype=jnp.float32):
    rng_or_u_ref, cube_ref, ew_ref, *rest = refs
    const_refs = rest[:-4]
    ms_ref, mc_ref, s1_ref, s2_ref = rest[-4:]
    i = pl.program_id(0)
    dtype = jnp.float32
    cube = cube_ref[...]                        # (block,) int32, sorted

    if rng_in_kernel:
        # This program's slice of uniform(fold_in(key, g), (chunk, d)) —
        # the SAME threefry routine as the TPU kernel, bit-exact under both
        # jax_threefry_partitionable layouts.
        u = vk._tile_uniforms(rng_or_u_ref[0, 0], rng_or_u_ref[0, 1],
                              i * block, block, chunk, d)     # (block, d)
    else:
        u = rng_or_u_ref[...]                                 # (block, d)

    valid = cube < n_cubes                      # (block,)
    cube_c = jnp.minimum(cube, n_cubes - 1)

    # ---- transform: stratified decode -> map gather -> Jacobian ----
    x_cols = []
    iys = []
    logjac = jnp.zeros((block,), dtype)
    for k in range(d):
        c_k = (cube_c // (nstrat**k)) % nstrat                # (block,)
        y_k = (c_k.astype(dtype) + u[:, k]) / nstrat
        yn = y_k * ninc
        iy_k = jnp.clip(yn.astype(jnp.int32), 0, ninc - 1)    # (block,)
        frac = yn - iy_k.astype(dtype)
        # Pointer gathers from the interleaved flat tables — the GPU-native
        # replacement for the TPU kernel's one-hot gather matvecs.
        e_lo = ew_ref[0, k * ninc + iy_k]                     # (block,)
        dx = ew_ref[1, k * ninc + iy_k]                       # (block,)
        x_cols.append(e_lo + frac * dx)
        iys.append(iy_k)
        logjac = logjac + jnp.log(jnp.maximum(ninc * dx, _TINY))

    x = jnp.stack(x_cols, axis=1)                             # (block, d)
    jac = jnp.exp(logjac)                                     # (block,)

    fx = integrand(x, *[r[...] for r in const_refs])
    fx = fx.reshape(block).astype(dtype)
    w = jnp.where(valid, jac * fx, jnp.zeros((), dtype))      # (block,)
    # §15 widening boundary: transform + integrand products are f32; the
    # per-eval contributions widen HERE, before the privatized masked-sum
    # partials, so both the in-block reductions and the HBM atomic
    # accumulators run at accum_dtype (which the budget model prices).
    accum = jnp.dtype(accum_dtype)
    w = w.astype(accum)
    w2 = w * w
    cnt = valid.astype(accum)

    # ---- map histogram: block-private partials, one atomic per bucket ----
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block, ninc), 1)
    for k in range(d):
        oh = iys[k][:, None] == lanes                         # (block, ninc)
        ms_k = jnp.sum(jnp.where(oh, w2[:, None], 0.0), axis=0)
        mc_k = jnp.sum(jnp.where(oh, cnt[:, None], 0.0), axis=0)
        idx = k * ninc + jax.lax.broadcasted_iota(jnp.int32, (ninc,), 0)
        # Indices are unique WITHIN this call (one per bucket); collisions
        # only happen across programs, which the atomics serialize.
        pl.atomic_add(ms_ref, (idx,), ms_k)
        pl.atomic_add(mc_ref, (idx,), mc_k)

    # ---- cube moments: windowed partials, one atomic per window slot ----
    # Sorted ids advance <= 1 per eval, so this block's ids live in
    # [cube_c[0], cube_c[0] + block); masked overflow evals clip into the
    # window but contribute exactly 0.
    base = cube_c[0]
    rel = jnp.clip(cube_c - base, 0, block - 1)               # (block,)
    wcols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ohc = rel[:, None] == wcols                               # (block, block)
    s1p = jnp.sum(jnp.where(ohc, w[:, None], 0.0), axis=0)    # (block,)
    s2p = jnp.sum(jnp.where(ohc, w2[:, None], 0.0), axis=0)
    cidx = base + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    pl.atomic_add(s1_ref, (cidx,), s1p)
    pl.atomic_add(s2_ref, (cidx,), s2p)


def vegas_fill_gpu(key_bits, cube, edges_lo, widths, *, nstrat: int,
                   n_cubes: int, integrand, block: int = 128,
                   interpret: bool = True, num_warps: int | None = None,
                   u=None, ig_consts=(), accum_dtype=None):
    """pallas_call wrapper for the Triton-shaped fill kernel (one chunk).

    Args:
      key_bits: (1, 2) uint32 raw key data of ``fold_in(key, gchunk)``.
      cube:     (chunk,) int32 SORTED cube ids; ``n_cubes`` == masked.
      edges_lo/widths: (d, ninc) f32 map tables.
      block:    evaluations per program (the CUDA block-size analogue);
                must divide ``chunk``.
      num_warps: Triton compiler knob (``TritonCompilerParams``); ignored
                by the interpreter, so interpret-mode CI exercises the same
                program the GPU compiles.
      u:        optional (chunk, d) f32 uniforms.  ``None`` generates them
                IN-KERNEL from ``key_bits``; passing the precomputed block
                is the interpret-mode escape hatch (same XLA:CPU threefry
                vectorization issue as the TPU path, DESIGN.md §7) —
                bit-identical either way.
      accum_dtype: accumulator dtype (default f32).  Under the §15 widened
                policy the four flat HBM accumulators are f64: per-eval
                products stay f32, each program widens its contributions
                before the privatized masked sums, and the atomic adds land
                on 8-byte slots.

    Returns flat ``(ms, mc, s1_pad, s2_pad)``: map moments as (d*ninc,) and
    cube moments as (n_cubes + block,) — reshape/trim in the caller.  All
    four are zero-initialized inputs aliased to outputs: the race-free init
    under a parallel grid (the TPU kernel's ``@pl.when(i == 0)`` writes
    would race here).
    """
    chunk = cube.shape[0]
    d, ninc = edges_lo.shape
    assert chunk % block == 0, (chunk, block)
    assert edges_lo.dtype == jnp.float32, \
        "pallas-gpu is f32-only (RNG contract)"
    accum = jnp.dtype(accum_dtype) if accum_dtype is not None else jnp.float32
    n_pad = n_cubes + block
    rng_in_kernel = u is None
    # Interleaved flat tables: row 0 = edges, row 1 = widths, each (d*ninc,)
    # so dimension k's interval j sits at flat index k*ninc + j.
    ew = jnp.stack([edges_lo.reshape(-1), widths.reshape(-1)])
    kig, flat_consts, const_specs = vk._const_transport(integrand, ig_consts)

    kernel = functools.partial(
        _fill_gpu_kernel, nstrat=nstrat, n_cubes=n_cubes, ninc=ninc,
        chunk=chunk, block=block, d=d, integrand=kig,
        rng_in_kernel=rng_in_kernel, accum_dtype=accum)
    grid = (chunk // block,)
    first_in = (key_bits, pl.BlockSpec((1, 2), lambda i: (0, 0))) \
        if rng_in_kernel else (u, pl.BlockSpec((block, d), lambda i: (i, 0)))

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, _n=len(shape): (0,) * _n)

    zeros = (jnp.zeros((d * ninc,), accum),
             jnp.zeros((d * ninc,), accum),
             jnp.zeros((n_pad,), accum),
             jnp.zeros((n_pad,), accum))
    n_in = 3 + len(flat_consts)     # positional index of the first zeros arg
    extra = {}
    if num_warps is not None:
        from jax.experimental.pallas import triton as plgpu
        extra["compiler_params"] = plgpu.TritonCompilerParams(
            num_warps=num_warps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            first_in[1],                                    # key bits | u
            pl.BlockSpec((block,), lambda i: (i,)),         # cube ids
            pl.BlockSpec((2, d * ninc), lambda i: (0, 0)),  # flat tables
            *const_specs,                                   # integrand consts
            full(d * ninc), full(d * ninc),                 # zeros: ms, mc
            full(n_pad), full(n_pad),                       # zeros: s1, s2
        ],
        out_specs=[full(d * ninc), full(d * ninc), full(n_pad), full(n_pad)],
        out_shape=[
            jax.ShapeDtypeStruct((d * ninc,), accum),
            jax.ShapeDtypeStruct((d * ninc,), accum),
            jax.ShapeDtypeStruct((n_pad,), accum),
            jax.ShapeDtypeStruct((n_pad,), accum),
        ],
        input_output_aliases={n_in: 0, n_in + 1: 1, n_in + 2: 2, n_in + 3: 3},
        interpret=interpret,
        **extra,
    )(first_in[0], cube, ew, *flat_consts, *zeros)


def fill(edges, n_h, key, integrand, *, nstrat: int, n_cap: int, chunk: int,
         dtype=jnp.float32, accum_dtype=None, interpret: bool | None = None,
         block: int | None = None, num_warps: int | None = None,
         start_chunk=0, n_chunks: int | None = None, kahan: bool = False,
         return_comp: bool = False, rng_in_kernel: bool | None = None):
    """GPU-kernel fill returning ``core.fill.FillResult``, scan-chunked
    exactly like ``ops.fill``: chunk ``g`` draws from ``fold_in(key, g)``
    and ``start_chunk``/``n_chunks`` select a contiguous chunk range (the
    unit ``dist.sharded_fill`` distributes, DESIGN.md C5) — so the sharding,
    batching, and early-stop machinery compose with this backend unchanged.

    ``interpret=None`` autodetects with family='gpu': compiled Triton on a
    GPU platform, the Pallas interpreter elsewhere (CPU CI).
    ``rng_in_kernel=None`` resolves to ``not interpret`` — same XLA:CPU
    threefry escape hatch as the TPU path, bit-identical either way.
    ``accum_dtype``/``return_comp`` follow the shared contract documented on
    ``ops.fill`` (§15 widened accumulation; Kahan compensation pair for the
    shard boundary).
    """
    from repro.core.fill import FillResult
    from .ops import hoist_closure, key_bits

    if return_comp and not kahan:
        raise ValueError("return_comp=True requires kahan=True (there is "
                         "no compensation term to return)")
    interpret = resolve_interpret(interpret, family="gpu")
    if rng_in_kernel is None:
        rng_in_kernel = not interpret
    dtype = jnp.dtype(dtype)
    accum = jnp.dtype(accum_dtype) if accum_dtype is not None else dtype
    if dtype != jnp.float32:
        raise ValueError(
            f"pallas-gpu is f32-only (the in-kernel RNG reproduces the f32 "
            f"uniform bit pattern; widen accum_dtype instead, §15); "
            f"got dtype={dtype}")
    if accum not in (jnp.float32, jnp.float64):
        raise ValueError(f"accum_dtype must be float32 or float64, "
                         f"got {accum}")
    d = edges.shape[0]
    ninc = edges.shape[1] - 1
    n_cubes = n_h.shape[0]
    if n_chunks is None:
        assert n_cap % chunk == 0, (n_cap, chunk)
        n_chunks = n_cap // chunk
    block = _pick_block(block, chunk, d, ninc, accum.itemsize)

    edges_lo = edges[:, :-1].astype(dtype)
    widths = jnp.diff(edges, axis=1).astype(dtype)
    pure_ig, ig_consts = hoist_closure(integrand, (block, d), dtype)

    def chunk_contrib(gchunk, cube):
        k = jax.random.fold_in(key, gchunk)
        u = (None if rng_in_kernel else
             jax.random.uniform(k, (chunk, d), dtype=dtype))
        ms, mc, s1p, s2p = vegas_fill_gpu(
            key_bits(k).reshape(1, 2), cube, edges_lo, widths,
            nstrat=nstrat, n_cubes=n_cubes, integrand=pure_ig, block=block,
            interpret=interpret, num_warps=num_warps, u=u,
            ig_consts=ig_consts, accum_dtype=accum)
        return FillResult(ms.reshape(d, ninc), mc.reshape(d, ninc),
                          s1p[:n_cubes], s2p[:n_cubes])

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
