"""Sharding mechanics: partition the fill's global chunk axis over a mesh.

The distribution contract is DESIGN.md C5: chunk ``g`` draws its samples from
``fold_in(key_it, g)`` and finds its cubes from the global offset
``g * chunk``, so a shard's numbers are a pure function of ``(key, chunk
range)`` — independent of device identity, count, or order.  Sharding is a
static partition of ``range(n_cap // chunk)`` plus one psum.

Two composition shapes, both built on :func:`make_local_fill`:

  * :func:`make_sharded_fill` wraps ONE fill call in its own ``shard_map`` —
    a drop-in ``fill_fn`` for `core.integrator.iteration_step` (what
    `repro.dist` re-exports, and what the host-loop/checkpoint path uses);
  * the executor's sharded **batched** program instead wraps the ENTIRE
    vmapped run in one ``shard_map`` and calls the local fill inside it —
    B scenarios × D devices as one jitted program (DESIGN.md §9.3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs

from . import backends as backends_mod

REPLICATED = P()


def mesh_shard_count(mesh, axis_names) -> int:
    """Number of fill shards = product of the mesh extents being sharded over."""
    n = 1
    for a in axis_names:
        n *= mesh.shape[a]
    return n


def shard_chunk_range(total_chunks: int, shard: int, n_shards: int):
    """Contiguous chunk range ``[start, start + count)`` owned by ``shard``.

    Every shard gets the same static ``count`` (ceil division) so all devices
    compile and run the identical scanned program; shards whose range extends
    past ``total_chunks`` simply accumulate zeros there (overflow-bucket
    masking, DESIGN.md C2).  Ranges partition ``[0, n_shards * count)`` and
    are disjoint, so summing every shard's partial reproduces the global fill.
    """
    count = -(-total_chunks // n_shards)
    return shard * count, count


def fill_lanes(rcfg, n_shards: int) -> int:
    """Lanes one fill runs over all its shards: ``n_shards`` equal static
    chunk ranges (the last may run past ``n_cap`` on dead chunks), or
    ``n_cap`` unsharded."""
    _, per_shard = shard_chunk_range(rcfg.n_cap // rcfg.chunk, 0, n_shards)
    return n_shards * per_shard * rcfg.chunk


def psum_bytes(rcfg) -> int:
    """Bytes each device all-reduces in one sharded fill: the partial
    :class:`FillResult` (two ``(d, ninc)`` map moments, two ``(n_cubes,)``
    cube moments) and its Kahan compensation, in the accumulation dtype."""
    prec = getattr(rcfg.execution, "precision", None)
    accum = (prec.accum_dtype if prec is not None
             and prec.accum_dtype is not None else rcfg.dtype)
    per_result = 2 * rcfg.dim * rcfg.ninc + 2 * rcfg.n_cubes
    return 2 * per_result * jnp.dtype(accum).itemsize


def linear_shard_index(mesh, axis_names):
    """Row-major linear shard index over the named mesh axes.  Only valid
    inside a ``shard_map`` body over those axes."""
    idx = jnp.zeros((), jnp.int32)
    for a in axis_names:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def make_local_fill(rcfg, mesh, axis_names, *, backend: str | None = None):
    """The per-shard fill + psum, for use INSIDE a ``shard_map`` body.

    ``fill(edges, n_h, key, integrand)`` computes this shard's chunk range
    with the registered backend (Kahan-compensated so partials are exact to
    ~1 ulp, DESIGN.md D4) and psum-reduces over ``axis_names`` — every
    device returns the identical replicated :class:`FillResult`.

    The compensation survives the shard boundary: each shard returns its
    ``(sums, comp)`` pair (``return_comp=True``) and BOTH are psum-reduced,
    so the combined result is ``psum(sums) - psum(comp)`` — the corrected
    total.  Psumming the raw sums alone would throw the per-shard
    compensations away at exactly the reduction step the Kahan carry exists
    to protect, re-introducing device-count-dependent drift at hostile
    scales (DESIGN.md §15).
    """
    axis_names = tuple(axis_names)
    n_shards = mesh_shard_count(mesh, axis_names)
    total_chunks = rcfg.n_cap // rcfg.chunk
    _, per_shard = shard_chunk_range(total_chunks, 0, n_shards)
    shard_fill = backends_mod.bind_fill(rcfg, backend=backend, kahan=True,
                                        return_comp=True)

    def fill(edges, n_h, key, integrand):
        idx = linear_shard_index(mesh, axis_names)
        part, comp = shard_fill(edges, n_h, key, integrand,
                                start_chunk=idx * per_shard,
                                n_chunks=per_shard)
        with obs.scope("vegas.psum"):
            total = jax.tree.map(lambda x: jax.lax.psum(x, axis_names), part)
            resid = jax.tree.map(lambda x: jax.lax.psum(x, axis_names), comp)
        return jax.tree.map(jnp.subtract, total, resid)

    return fill


def replicated_shard_map(body, mesh, n_args: int):
    """Wrap ``body`` in a replicated-in / replicated-out ``shard_map``.

    ``check_vma=False``: ``pallas_call`` has no replication rule under
    shard_map, and the psum inside the body already replicates every output
    explicitly (each device computes the identical O(KB) adaptation state;
    only the fill is divided).
    """
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(REPLICATED,) * n_args,
                         out_specs=REPLICATED, check_vma=False)


def make_stop_sync(axis_names):
    """All-shards agreement on the adaptive loop's continue decision (§10).

    For use INSIDE a ``shard_map`` body that runs the stop-policy
    ``while_loop`` (the sharded batched program): ``sync(cont)`` pmin-reduces
    the boolean over ``axis_names``, so the loop continues only while EVERY
    shard wants to.  Each shard computes the identical replicated statistics
    (the fill is already psum-reduced), making the reduction a formality —
    but the explicit agreement guarantees the while_loop trip count cannot
    diverge across devices even if a backend's reduction order ever did.

    The single-scenario sharded path needs no sync: there the ``shard_map``
    wraps only the fill, the while_loop runs outside it on replicated
    values, and no mesh axis is in scope at the decision point.
    """
    axis_names = tuple(axis_names)

    def sync(cont):
        return jax.lax.pmin(cont.astype(jnp.int32), axis_names) > 0

    return sync


def make_sharded_fill(mesh, axis_names, resolved_cfg,
                      backend: str | None = None):
    """Build a drop-in ``fill_fn`` for ``core.integrator.iteration_step``.

    ``fill_fn(edges, n_h, key, integrand)`` shard_maps the configured fill
    backend (default: the config's own) over the mesh axes named in
    ``axis_names`` and psum-reduces the per-shard partials, returning the
    same replicated result on every device.  Works eagerly and under jit
    (``run`` jits the whole iteration around it, so adaptation stays
    on-device, C4/C6).
    """
    rc = resolved_cfg
    axis_names = tuple(axis_names)
    local_fill = make_local_fill(rc, mesh, axis_names, backend=backend)

    def fill_fn(edges, n_h, key, integrand):
        body = lambda e, nh, k: local_fill(e, nh, k, integrand)
        return replicated_shard_map(body, mesh, 3)(edges, n_h, key)

    return fill_fn
