"""Backend parity sweep: ``fill_pallas`` (interpret mode, both the P-V2
baseline and the P-V3 fused streaming kernel) vs ``fill_reference`` across
dimensions, stratification counts, and non-power-of-two chunk/tile shapes.

All paths share the chunk-keyed RNG contract (DESIGN.md C5) — the in-kernel
backends regenerate the stream bit-for-bit — so they draw IDENTICAL
samples: tolerances cover accumulation-order f32 drift only, never
sampling differences."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fill as fill_mod
from repro.core import map as vmap_
from repro.core import strat


def _ig(x):
    return jnp.prod(1.0 / (0.1 + (x - 0.3) ** 2), axis=-1)


def _assert_fill_parity(dim, nstrat, chunk, n_chunks, tile, ninc=32,
                        adapted=True, neval=None):
    n_cubes = nstrat**dim
    n_cap = chunk * n_chunks
    key = jax.random.PRNGKey(dim * 100 + nstrat)
    if adapted:
        # a non-uniform (adapted-looking) map stresses the gather paths
        w = jax.random.uniform(jax.random.fold_in(key, 1), (dim, ninc),
                               minval=0.05, maxval=1.0)
        w = w / w.sum(1, keepdims=True)
        edges = jnp.concatenate(
            [jnp.zeros((dim, 1)), jnp.cumsum(w, axis=1)], axis=1)
    else:
        edges = vmap_.uniform_edges([0.0] * dim, [1.0] * dim, ninc)
    if neval is None:
        neval = max(n_cap - n_cubes, n_cubes * 2)
    n_h = strat.uniform_nh(neval, n_cubes)

    ref = fill_mod.fill_reference(edges, n_h, key, _ig, nstrat=nstrat,
                                  n_cap=n_cap, chunk=chunk)
    # (fused, rng_in_kernel): P-V2 baseline, P-V3 hybrid (CPU default), and
    # P-V3 with in-kernel RNG (the compiled-TPU program, run interpreted).
    for fused, rng in ((False, None), (True, None), (True, True)):
        pal = fill_mod.fill_pallas(edges, n_h, key, _ig, nstrat=nstrat,
                                   n_cap=n_cap, chunk=chunk, interpret=True,
                                   fused_cubes=fused, tile=tile,
                                   rng_in_kernel=rng)
        for field in ("map_sums", "map_counts", "cube_s1", "cube_s2"):
            a = np.asarray(getattr(ref, field))
            b = np.asarray(getattr(pal, field))
            scale = np.abs(a).max() or 1.0
            np.testing.assert_allclose(
                b, a, rtol=1e-4, atol=1e-5 * scale,
                err_msg=f"{field} fused={fused} rng_in_kernel={rng} dim={dim} "
                        f"nstrat={nstrat} chunk={chunk} tile={tile}")


@pytest.mark.parametrize("dim", [1, 2, 4])
@pytest.mark.parametrize("nstrat", [1, 2, 5])
def test_fill_parity_dim_nstrat_sweep(dim, nstrat):
    _assert_fill_parity(dim, nstrat, chunk=512, n_chunks=2, tile=256)


@pytest.mark.parametrize("chunk,n_chunks,tile", [
    (96, 3, 256),    # n_local=288 not a tile multiple -> divisor fallback (96)
    (384, 2, 256),   # tile | n_local but not chunk: tiles cross chunk bounds
    (100, 4, 50),    # nothing a power of two
    (768, 1, 256),   # single chunk, exact tiling
])
def test_fill_parity_non_pow2_chunk_tile(chunk, n_chunks, tile):
    _assert_fill_parity(dim=2, nstrat=3, chunk=chunk, n_chunks=n_chunks,
                        tile=tile)


def test_fill_parity_uniform_map_exactish():
    """Uniform map + nstrat=1: the transform is the identity; the two
    backends agree to strict tolerance."""
    _assert_fill_parity(dim=2, nstrat=1, chunk=256, n_chunks=2, tile=128,
                        adapted=False)


def test_fill_parity_odd_chunk_times_dim():
    """chunk*d odd exercises the padded-counter branch of the in-kernel RNG
    (jax pads one zero before splitting the iota into cipher halves)."""
    _assert_fill_parity(dim=3, nstrat=2, chunk=45, n_chunks=3, tile=45)


def test_fill_parity_masked_tail_heavy():
    """Most of the eval axis past the active total: whole tiles of overflow
    evals at the n_cap pad must contribute exactly zero in every backend."""
    dim, nstrat, chunk, n_chunks = 2, 3, 256, 4
    n_cubes = nstrat**dim
    # active total ~ one third of n_cap: the last ~2.7 chunks are all-masked
    _assert_fill_parity(dim, nstrat, chunk, n_chunks, tile=64,
                        neval=max(chunk * n_chunks // 3, 2 * n_cubes))


def test_fill_parity_cubes_not_tile_multiple():
    """n_cubes (3^4 = 81) far from any tile multiple: the fused kernel's
    LANE-padded accumulator must trim back to exactly n_cubes."""
    _assert_fill_parity(dim=4, nstrat=3, chunk=512, n_chunks=2, tile=128)


@pytest.mark.parametrize("fused,rng", [(False, None), (True, None),
                                       (True, True)])
def test_fill_parity_chunk_subrange_traced_start(fused, rng):
    """A chunk sub-range (the shard unit) with a traced ``start_chunk``:
    the kernel fill reads each chunk's ids at the range's offset, as
    ``fill_reference`` does on the same range.  The allocation has
    zero-size cubes and a masked tail that starts inside the range."""
    dim, nstrat, chunk, n_chunks, ninc = 2, 5, 256, 6, 32
    n_cubes, n_cap = nstrat**dim, chunk * n_chunks
    key = jax.random.PRNGKey(11)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (dim, ninc),
                           minval=0.05, maxval=1.0)
    w = w / w.sum(1, keepdims=True)
    edges = jnp.concatenate([jnp.zeros((dim, 1)), jnp.cumsum(w, axis=1)], 1)
    n_h = jax.random.randint(jax.random.fold_in(key, 2), (n_cubes,), 0, 90,
                             dtype=jnp.int32)
    n_h = n_h.at[::4].set(0)
    assert 2 * chunk < int(n_h.sum()) < 5 * chunk
    kw = dict(nstrat=nstrat, n_cap=n_cap, chunk=chunk, n_chunks=3)

    ref = fill_mod.fill_reference(edges, n_h, key, _ig, start_chunk=2, **kw)
    pal = jax.jit(lambda s: fill_mod.fill_pallas(
        edges, n_h, key, _ig, interpret=True, fused_cubes=fused, tile=64,
        rng_in_kernel=rng, start_chunk=s, **kw))(jnp.int32(2))
    head = fill_mod.fill_reference(edges, n_h, key, _ig, start_chunk=0, **kw)
    for field in ("map_sums", "map_counts", "cube_s1", "cube_s2"):
        a = np.asarray(getattr(ref, field))
        b = np.asarray(getattr(pal, field))
        scale = np.abs(a).max() or 1.0
        np.testing.assert_allclose(
            b, a, rtol=1e-4, atol=1e-5 * scale,
            err_msg=f"{field} fused={fused} rng_in_kernel={rng}")
        # The offset matters: the range's sums are not the first range's.
        assert not np.allclose(a, np.asarray(getattr(head, field)))


@pytest.mark.parametrize("fused", [False, True])
def test_backend_configs_agree_through_full_run(fused):
    """End-to-end: a full adapted run under each backend lands within
    combined statistical error (identical streams, different accumulation)."""
    from repro.core import VegasConfig, run
    from repro.core import integrands as igs
    ig = igs.make_cosine(dim=3)
    kw = dict(neval=12_000, max_it=6, skip=2, ninc=32, chunk=4096)
    r_ref = run(ig, VegasConfig(backend="ref", **kw), key=jax.random.PRNGKey(4))
    r_pal = run(ig, VegasConfig(backend="pallas", fused_cubes=fused, **kw),
                key=jax.random.PRNGKey(4))
    comb = float(np.hypot(r_ref.sdev, r_pal.sdev))
    assert abs(r_ref.mean - r_pal.mean) < 3 * comb
