"""The fill phase: sample -> transform -> evaluate -> accumulate.

This is cuVegas' ``vegasFill`` (Alg. 2) — the kernel that dominates runtime
(paper Table 1: 36-99% of total).  The decomposition is the paper's C1:
a flat axis of ``n_cap`` evaluations, each knowing its hypercube, processed
in fixed-size batches so the work per lane is identical (no divergence).

Three interchangeable backends with one contract:
  * ``ref``    — pure jnp oracle (scatter-add accumulation),
  * ``pallas`` — the TPU kernel (kernels/vegas_fill.py) for transform + eval +
                 MXU one-hot map accumulation; cube reduction via segment-sum,
  * both are chunked with ``lax.scan`` so the live working set stays bounded
    (the TPU analogue of the paper's batch_size knob).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs

from . import map as vmap_
from . import strat


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FillResult:
    """Accumulators produced by one fill pass (paper's map/cube weights)."""
    map_sums: jax.Array    # (d, ninc)   sum of (J f)^2 per map interval
    map_counts: jax.Array  # (d, ninc)   number of samples per map interval
    cube_s1: jax.Array     # (n_cubes,)  sum of J f per hypercube
    cube_s2: jax.Array     # (n_cubes,)  sum of (J f)^2 per hypercube

    def tree_flatten(self):
        return (self.map_sums, self.map_counts, self.cube_s1, self.cube_s2), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __add__(self, other):
        return FillResult(self.map_sums + other.map_sums,
                          self.map_counts + other.map_counts,
                          self.cube_s1 + other.cube_s1,
                          self.cube_s2 + other.cube_s2)


def _eval_chunk(edges, cube, u, integrand, nstrat, n_cubes):
    """Transform + evaluate one chunk. Returns (w, iy, valid)."""
    valid = cube < n_cubes
    y = strat.stratified_y(jnp.minimum(cube, n_cubes - 1), u, nstrat)
    x, jac, iy = vmap_.apply_map(edges, y)
    fx = integrand(x)
    w = jnp.where(valid, jac * fx, 0.0)
    return w, iy, valid


def fill_reference(edges, n_h, key, integrand, *, nstrat: int, n_cap: int,
                   chunk: int, dtype=jnp.float32, accum_dtype=None,
                   start_chunk=0, n_chunks: int | None = None,
                   kahan: bool = False,
                   return_comp: bool = False) -> FillResult:
    """Pure-jnp fill, scanned in chunks of the *global* eval axis.

    ``start_chunk``/``n_chunks`` select a contiguous chunk range — the unit of
    distribution.  The RNG is keyed by the GLOBAL chunk index, so the stream a
    shard produces is a pure function of (key, chunk id): any device can
    (re)compute any shard — the basis for elastic scaling and straggler
    re-dispatch (DESIGN.md C5/D3).

    ``kahan=True`` carries a Kahan compensation term through the scan, making
    the accumulated sums independent (to ~1 ulp) of how the chunk range is
    grouped.  The sharded fill turns this on so a fill split over 2 devices
    and one split over 8 agree far inside the 2e-5 invariance tolerance —
    without it, plain-f32 reduction-order drift is amplified by the adaptation
    feedback across iterations (DESIGN.md §5).

    ``accum_dtype`` (default: ``dtype``) is the §15 accumulation dtype:
    samples and integrand products stay in ``dtype``, but each chunk's
    contributions are widened BEFORE the scatter-adds, so both the
    within-chunk and the cross-chunk accumulation run at the wider
    precision — the reference semantics the kernel backends approximate.

    ``return_comp=True`` (requires ``kahan=True``) returns the
    ``(sums, compensation)`` FillResult pair instead of the sums alone: the
    shard boundary needs BOTH so the psum can carry the compensation across
    devices (``engine.sharding.make_local_fill``) instead of silently
    degrading to naive summation there.
    """
    if return_comp and not kahan:
        raise ValueError("return_comp=True requires kahan=True (there is "
                         "no compensation term to return)")
    accum = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else jnp.dtype(dtype)
    dim = edges.shape[0]
    ninc = edges.shape[1] - 1
    n_cubes = n_h.shape[0]
    assert n_cap % chunk == 0, (n_cap, chunk)
    if n_chunks is None:
        n_chunks = n_cap // chunk

    def body(carry, xs):
        step, cube = xs
        acc, comp = carry if kahan else (carry, None)
        k = jax.random.fold_in(key, start_chunk + step)
        u = jax.random.uniform(k, (chunk, dim), dtype=dtype)
        w, iy, valid = _eval_chunk(edges, cube, u, integrand, nstrat, n_cubes)
        w = w.astype(accum)
        w2 = w * w
        cnt = valid.astype(accum)
        ms, mc = vmap_.accumulate_map_weights(iy, w2, cnt, ninc)
        # Overflow bucket (id n_cubes) catches masked evals; dropped below.
        s1 = jnp.zeros((n_cubes + 1,), accum).at[cube].add(w)
        s2 = jnp.zeros((n_cubes + 1,), accum).at[cube].add(w2)
        contrib = FillResult(ms, mc, s1[:n_cubes], s2[:n_cubes])
        if not kahan:
            return acc + contrib, None
        y = jax.tree.map(jnp.subtract, contrib, comp)
        t = jax.tree.map(jnp.add, acc, y)
        comp = jax.tree.map(lambda tt, a, yy: (tt - a) - yy, t, acc, y)
        return (t, comp), None

    zero = FillResult(jnp.zeros((dim, ninc), accum), jnp.zeros((dim, ninc), accum),
                      jnp.zeros((n_cubes,), accum), jnp.zeros((n_cubes,), accum))
    init = (zero, zero) if kahan else zero
    # The range's cube ids in one pass (4 bytes a lane), read a chunk a step.
    cubes = strat.cubes_for_slice(n_h, start_chunk * chunk, n_chunks * chunk)
    out, _ = jax.lax.scan(body, init, (jnp.arange(n_chunks),
                                       cubes.reshape(n_chunks, chunk)))
    if kahan:
        return out if return_comp else out[0]
    return out


def fill_pallas(edges, n_h, key, integrand, *, nstrat: int, n_cap: int,
                chunk: int, dtype=jnp.float32, accum_dtype=None,
                interpret: bool | None = None,
                fused_cubes: bool = True, tile: int | None = None,
                start_chunk=0, n_chunks: int | None = None,
                kahan: bool = False, return_comp: bool = False,
                rng_in_kernel: bool | None = None) -> FillResult:
    """Pallas-kernel fill, scan-chunked like :func:`fill_reference` (same
    ``start_chunk``/``n_chunks`` distribution unit, same chunk-keyed RNG with
    bit-identical streams).  ``fused_cubes=True`` (default) runs the P-V3
    streaming kernel: in-kernel RNG + in-kernel cube accumulation, no per-eval
    array anywhere.  ``interpret=None`` autodetects (compiled on TPU,
    interpreter elsewhere); ``tile=None`` autotunes against the VMEM budget."""
    from repro.kernels import ops as kops
    return kops.fill(edges, n_h, key, integrand, nstrat=nstrat, n_cap=n_cap,
                     chunk=chunk, dtype=dtype, accum_dtype=accum_dtype,
                     interpret=interpret,
                     fused_cubes=fused_cubes, tile=tile,
                     start_chunk=start_chunk, n_chunks=n_chunks, kahan=kahan,
                     return_comp=return_comp, rng_in_kernel=rng_in_kernel)


def estimate_from_cubes(res: FillResult, n_h: jax.Array):
    """Iteration estimate + variance + stratification signal (eq. (5)-(7)).

    Each cube has y-volume v = 1/n_cubes; I_h = v * mean(Jf), and the variance
    of the cube mean is v^2 (E[w^2]-E[w]^2)/(n_h-1).
    Returns (I_it, sigma2_it, d_h) with d_h = per-cube sample sigma — the
    allocation signal n_h ∝ d_h^beta ("n_h proportional to sigma_h(Jf)").
    """
    with obs.scope("vegas.estimate"):
        n_cubes = n_h.shape[0]
        nh = jnp.maximum(n_h.astype(res.cube_s1.dtype), 1.0)
        v = 1.0 / n_cubes
        m = res.cube_s1 / nh
        q = res.cube_s2 / nh
        var = jnp.maximum(q - m * m, 0.0)
        i_it = v * jnp.sum(m)
        sigma2 = v * v * jnp.sum(var / jnp.maximum(nh - 1.0, 1.0))
        d_h = jnp.sqrt(var)
        return i_it, sigma2, d_h
