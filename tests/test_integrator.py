"""Integration tests: does the integrator integrate (paper §4 claims at
test scale)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import VegasConfig, run
from repro.core import integrands as igs


FAST = VegasConfig(neval=60_000, max_it=12, skip=4, ninc=128, chunk=16384)


@pytest.mark.parametrize("maker,kw", [
    (igs.make_sine_exp, {}),
    (igs.make_linear, {}),
    (igs.make_cosine, {}),
    (igs.make_roos_arnold, {}),
    (igs.make_morokoff_caflisch, {}),
])
def test_table3_easy_integrands_converge(maker, kw):
    ig = maker(**kw)
    r = run(ig, FAST, key=jax.random.PRNGKey(7))
    pull = (r.mean - ig.target) / r.sdev
    assert abs(pull) < 5, (ig.name, r, ig.target)
    assert r.sdev / abs(ig.target) < 5e-2


def test_peaked_gaussian_converges_with_adaptation():
    ig = igs.make_gaussian()
    cfg = VegasConfig(neval=300_000, max_it=12, skip=5, ninc=256, chunk=65536)
    r = run(ig, cfg, key=jax.random.PRNGKey(1))
    pull = (r.mean - ig.target) / r.sdev
    assert abs(pull) < 5
    assert r.chi2_dof < 5


def test_ridge_stratification_beats_uniform():
    """Paper Fig. 8: adaptive stratification (beta>0) reduces the variance on
    diagonal-structured integrands vs beta=0 (classic VEGAS / m-CUBES)."""
    ig = igs.make_ridge(n_peaks=50)
    kw = dict(neval=80_000, max_it=12, skip=5, ninc=128, chunk=16384)
    r_plus = run(ig, VegasConfig(beta=0.75, **kw), key=jax.random.PRNGKey(3))
    r_zero = run(ig, VegasConfig(beta=0.0, **kw), key=jax.random.PRNGKey(3))
    assert abs(r_plus.mean - ig.target) / r_plus.sdev < 5
    # stratified sdev should not be worse; typically clearly better.
    assert r_plus.sdev < 1.5 * r_zero.sdev


def test_iteration_aggregation_weights_by_variance():
    from repro.core.integrator import combine_results
    res = jnp.array([[1.0, 1e-4], [3.0, 1e-2]])  # second has 100x variance
    mean, sdev, chi2, n = combine_results(res, skip=0, n_done=2)
    assert abs(float(mean) - (1.0 / 1e-4 + 3.0 / 1e-2) / (1 / 1e-4 + 1 / 1e-2)) < 1e-6
    assert float(sdev) == pytest.approx(np.sqrt(1.0 / (1 / 1e-4 + 1 / 1e-2)), rel=1e-5)
    assert int(n) == 2


def test_combine_results_all_unusable_is_nan_free():
    """Every iteration has inf/non-finite sig2 (wsum == 0): the combination
    must return the (0.0, inf, 0.0, 0) sentinel, never NaN."""
    from repro.core.integrator import combine_results
    for bad in (np.inf, np.nan, 0.0):
        res = jnp.array([[1.0, bad], [2.0, bad], [3.0, bad]])
        mean, sdev, chi2, n = combine_results(res, skip=0, n_done=3)
        assert float(mean) == 0.0
        assert float(sdev) == np.inf
        assert float(chi2) == 0.0
        assert int(n) == 0
        assert not np.isnan(float(mean))
        assert not np.isnan(float(chi2))


def test_combine_results_skip_beyond_n_done_is_nan_free():
    from repro.core.integrator import combine_results
    res = jnp.array([[1.0, 1e-4], [2.0, 1e-4]])
    mean, sdev, chi2, n = combine_results(res, skip=5, n_done=2)
    assert (float(mean), float(chi2), int(n)) == (0.0, 0.0, 0)
    assert float(sdev) == np.inf


def test_skip_excludes_warmup():
    from repro.core.integrator import combine_results
    res = jnp.array([[100.0, 1e-6], [1.0, 1e-4], [1.0, 1e-4]])
    mean, _, _, n = combine_results(res, skip=1, n_done=3)
    assert abs(float(mean) - 1.0) < 1e-6
    assert int(n) == 2


def test_resume_from_state_matches_uninterrupted():
    """Fault-tolerance: stop after k iterations, resume from the state, and
    get the SAME final answer as the uninterrupted run."""
    ig = igs.make_cosine(dim=4)
    cfg = VegasConfig(neval=20_000, max_it=8, skip=2, ninc=64, chunk=4096)
    key = jax.random.PRNGKey(11)
    full = run(ig, cfg, key=key)

    cfg_half = VegasConfig(neval=20_000, max_it=4, skip=2, ninc=64, chunk=4096)
    half = run(ig, cfg_half, key=key)
    resumed = run(ig, cfg, key=key, state=half.state)
    assert resumed.mean == pytest.approx(full.mean, rel=1e-6)
    assert resumed.sdev == pytest.approx(full.sdev, rel=1e-6)


def test_pallas_backend_statistically_consistent():
    ig = igs.make_cosine(dim=4)
    kw = dict(neval=20_000, max_it=8, skip=3, ninc=64, chunk=4096)
    r = run(ig, VegasConfig(backend="pallas", **kw), key=jax.random.PRNGKey(5))
    pull = (r.mean - ig.target) / r.sdev
    assert abs(pull) < 5


def test_importance_only_mode():
    # nstrat=1: single cube, pure adaptive importance sampling (VEGAS map only)
    ig = igs.make_gaussian(dim=2, sigma=0.1)
    cfg = VegasConfig(neval=40_000, max_it=10, skip=4, ninc=128, nstrat=1,
                      chunk=8192)
    r = run(ig, cfg, key=jax.random.PRNGKey(2))
    assert abs(r.mean - ig.target) / r.sdev < 5


@pytest.mark.parametrize("d", [1, 2, 10, 16])
def test_unrolled_column_scans_match_jnp(d):
    """prod_cols/cumsum_cols (what Mosaic can lower) == jnp.prod/cumsum."""
    x = jax.random.uniform(jax.random.PRNGKey(d), (37, d), minval=0.5,
                           maxval=1.5)
    np.testing.assert_allclose(igs.prod_cols(x), jnp.prod(x, axis=-1),
                               rtol=1e-6)
    np.testing.assert_allclose(igs.cumsum_cols(x), jnp.cumsum(x, axis=-1),
                               rtol=1e-6)
