"""Unit + property tests for adaptive stratification."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Property tests need hypothesis (requirements-dev.txt); skip the module —
# not the whole collection — where it is absent.
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import strat  # noqa: E402


@settings(max_examples=30, deadline=None)
@given(neval=st.integers(100, 10_000_000), dim=st.integers(1, 16))
def test_choose_nstrat_respects_cap(neval, dim):
    ns = strat.choose_nstrat(neval, dim, max_cubes=1 << 16)
    assert ns >= 1
    assert ns**dim <= 1 << 16 or ns == 1


def test_map_evals_to_cubes_matches_repeat():
    n_h = jnp.array([3, 0, 2, 5, 1], jnp.int32)
    n_cap = 16
    cube, used = strat.map_evals_to_cubes(n_h, n_cap)
    expected = np.repeat(np.arange(5), np.asarray(n_h))
    np.testing.assert_array_equal(np.asarray(cube[: len(expected)]), expected)
    assert int(used) == 11
    assert (np.asarray(cube[len(expected):]) == 5).all()  # overflow bucket


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**30), n_cubes=st.integers(1, 300))
def test_map_evals_to_cubes_property(seed, n_cubes):
    key = jax.random.PRNGKey(seed)
    n_h = jax.random.randint(key, (n_cubes,), 0, 7, dtype=jnp.int32)
    total = int(n_h.sum())
    n_cap = total + 13
    cube, used = strat.map_evals_to_cubes(n_h, n_cap)
    assert int(used) == total
    counts = np.bincount(np.asarray(cube), minlength=n_cubes + 1)
    np.testing.assert_array_equal(counts[:n_cubes], np.asarray(n_h))
    assert counts[n_cubes] == n_cap - total


#: Cube sizes with zero-size cubes, in runs and at either end: ends at
#: 0, 3, 3, 5, 5, 5, 10, 11, 11, 15, 15.
_N_H = np.array([0, 3, 0, 2, 0, 0, 5, 1, 0, 4, 0], np.int32)


@pytest.mark.parametrize("how", ["eager", "jit", "vmap"])
@pytest.mark.parametrize("length", [1, 13, 29])
@pytest.mark.parametrize("start", [
    0,    # the axis' start
    1,    # mid-cube
    3,    # on a boundary followed by a zero-size cube
    5,    # on a boundary followed by two zero-size cubes
    8,    # mid-cube
    14,   # the last active eval
    15,   # exactly the active total
    40,   # past the total: every id is the overflow id
])
def test_cubes_for_slice_matches_searchsorted(start, length, how):
    """The scatter-and-prefix-sum ids equal a searchsorted over
    cumsum(n_h) exactly, for every offset, with ``start`` traced under jit
    and with a batch of allocations under vmap."""
    def oracle(n_h):
        return np.searchsorted(np.cumsum(n_h), start + np.arange(length),
                               side="right")

    if how == "vmap":
        rng = np.random.default_rng(start * 100 + length)
        batch = np.stack([_N_H, _N_H[::-1], np.zeros_like(_N_H),
                          rng.integers(0, 4, _N_H.shape).astype(np.int32)])
        got = jax.vmap(lambda nh: strat.cubes_for_slice(nh, start, length))(
            jnp.asarray(batch))
        want = np.stack([oracle(nh) for nh in batch])
    else:
        f = lambda s: strat.cubes_for_slice(jnp.asarray(_N_H), s, length)
        if how == "jit":
            f = jax.jit(f)
        got = f(jnp.int32(start) if how == "jit" else start)
        want = oracle(_N_H)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_cube_coords_roundtrip():
    nstrat, dim = 4, 5
    ids = jnp.arange(nstrat**dim, dtype=jnp.int32)
    coords = strat.cube_coords(ids, nstrat, dim)
    pows = nstrat ** np.arange(dim)
    rec = (np.asarray(coords) * pows).sum(-1)
    np.testing.assert_array_equal(rec, np.asarray(ids))
    assert (np.asarray(coords) >= 0).all() and (np.asarray(coords) < nstrat).all()


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**30), beta=st.floats(0.0, 1.5))
def test_adapt_nh_invariants(seed, beta):
    key = jax.random.PRNGKey(seed)
    d_h = jax.random.uniform(key, (64,)) ** 3
    n_h = strat.adapt_nh(d_h, beta, neval=10_000)
    assert (np.asarray(n_h) >= 2).all()
    assert int(n_h.sum()) <= 10_000 + 2 * 64  # eval_capacity bound
    if beta == 0.0:  # uniform allocation
        assert len(np.unique(np.asarray(n_h))) == 1


def test_adapt_nh_allocates_to_high_variance():
    d_h = jnp.array([0.0, 0.1, 10.0, 0.1], jnp.float32)
    n_h = np.asarray(strat.adapt_nh(d_h, 0.75, neval=1000))
    assert n_h[2] > 10 * n_h[1]


@pytest.mark.parametrize("tiny", [0.0, 1.1754944e-38, 1e-31])
def test_adapt_nh_signal_free_total_allocates_uniformly(tiny):
    """A variance total at or under the normalizing clamp still hands out
    ~neval evaluations (uniformly), not just the per-cube floor."""
    d_h = jnp.full((4,), tiny / 4, jnp.float32)
    n_h = np.asarray(strat.adapt_nh(d_h, 1.0, neval=1000))
    np.testing.assert_array_equal(n_h, [250] * 4)


def test_stratified_y_stays_in_cube():
    key = jax.random.PRNGKey(3)
    nstrat, dim, n = 3, 4, 256
    cube = jax.random.randint(key, (n,), 0, nstrat**dim, dtype=jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (n, dim))
    y = strat.stratified_y(cube, u, nstrat)
    coords = strat.cube_coords(cube, nstrat, dim)
    assert (np.asarray(y) >= np.asarray(coords) / nstrat - 1e-7).all()
    assert (np.asarray(y) <= (np.asarray(coords) + 1) / nstrat + 1e-7).all()
