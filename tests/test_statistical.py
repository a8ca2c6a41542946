"""Statistical conformance suite (ISSUE 5): is the estimator CORRECT, not
just deterministic?

The rest of the suite proves determinism, backend parity, and engine
composition; nothing so far checked that the reported uncertainties mean
what they claim.  This module does, with three classic diagnostics from the
vegas literature:

  * **pull coverage** — over N independent seeded runs, the pulls
    ``(estimate - truth) / sdev`` must be ~ N(0, 1): coverage of the
    +-1.96 sigma interval inside binomial bounds, mean and width of the
    pull distribution near (0, 1).  The N runs execute as ONE vmapped
    program: an `IntegrandFamily` with N identical parameter rows gives N
    independent per-scenario RNG streams (``fold_in(key, b)``) over the
    same integrand — the conformance suite rides the batch engine.
  * **chi^2/dof sanity** — the per-run consistency diagnostic
    (`combine_results`) must sit in a sane band on well-behaved integrands;
    a tiny or huge value means the per-iteration sigmas are mis-scaled.
  * **1/sqrt(neval) scaling** — with adaptation frozen (alpha = beta = 0
    the loop is plain stratified MC), quadrupling ``neval`` must halve the
    combined sdev; with adaptation on, sdev must still shrink monotonically
    up the ladder.

Seeds come from ``REPRO_STATS_SEED`` (default 0) so CI can run a fixed seed
matrix (the `stats-smoke` job); every bound below is loose enough to hold
for any seed with overwhelming probability, yet tight enough that a
mis-scaled sdev or a biased estimator fails it immediately.
"""

import math
import os

import jax
import numpy as np
import pytest

from repro.batch import run_batch
from repro.batch.family import make_gaussian_family, make_ridge_family
from repro.core import VegasConfig, run
from repro.core import integrands as igs

SEED = int(os.environ.get("REPRO_STATS_SEED", "0"))
KEY = jax.random.PRNGKey(SEED)

#: Number of independent seeded runs per pull test (>= 50 per ISSUE 5).
N_RUNS = 50

#: Binomial bound for coverage of +-1.96 sigma at p=0.95, n=50: a true
#: N(0,1) pull distribution lands below 42/50 with probability ~2e-4.
MIN_COVERED = 42


def _pulls(family, cfg, key=KEY):
    res = run_batch(family, cfg, key=key)
    assert family.targets is not None
    return (res.mean - family.targets) / res.sdev, res


def _check_pulls(pulls, label):
    covered = int(np.sum(np.abs(pulls) <= 1.96))
    assert covered >= MIN_COVERED, (
        f"{label}: only {covered}/{len(pulls)} pulls within 1.96 sigma "
        f"(binomial floor {MIN_COVERED}) — sdev underestimates the error")
    # Mean ~ N(0, 1/sqrt(n)): 4.2 sigma bound.  A systematic bias (e.g. a
    # Jacobian error) shows up here long before it breaks coverage.
    assert abs(np.mean(pulls)) <= 4.2 / math.sqrt(len(pulls)), (
        f"{label}: pull mean {np.mean(pulls):+.3f} — biased estimator")
    # Width ~ 1 (loose: the sdev itself is an estimate and adaptation
    # correlates early iterations; 0.55/1.55 still catches factor-sqrt(2)
    # mis-scaling of the variance).
    assert 0.55 <= np.std(pulls) <= 1.55, (
        f"{label}: pull std {np.std(pulls):.3f} — mis-scaled sdev")


# --- pull-distribution coverage, one family per paper workload class ---------

def test_pull_coverage_gaussian_peak():
    fam = make_gaussian_family(np.full(N_RUNS, 0.5), dim=3, sigma=0.2)
    cfg = VegasConfig(neval=6_000, max_it=10, skip=5, ninc=64, chunk=2048)
    pulls, res = _pulls(fam, cfg)
    _check_pulls(np.asarray(pulls), "gaussian_peak")
    assert 0.3 <= float(np.mean(res.chi2_dof)) <= 3.0, res.chi2_dof


def test_pull_coverage_ridge():
    direction = np.tile([0.6, 0.8, 1.0], (N_RUNS, 1))
    fam = make_ridge_family(direction, dim=3, n_peaks=8)
    cfg = VegasConfig(neval=6_000, max_it=10, skip=5, ninc=64, chunk=2048)
    pulls, res = _pulls(fam, cfg)
    _check_pulls(np.asarray(pulls), "ridge")
    assert 0.3 <= float(np.mean(res.chi2_dof)) <= 3.0, res.chi2_dof


def test_pull_coverage_diagonal():
    """The paper's main-diagonal ridge: peaks along (1, ..., 1) — the
    workload stratification exists for (classic VEGAS' worst case)."""
    direction = np.ones((N_RUNS, 3))
    fam = make_ridge_family(direction, dim=3, n_peaks=8)
    cfg = VegasConfig(neval=6_000, max_it=10, skip=5, ninc=64, chunk=2048)
    pulls, res = _pulls(fam, cfg)
    _check_pulls(np.asarray(pulls), "diagonal")
    assert 0.3 <= float(np.mean(res.chi2_dof)) <= 3.0, res.chi2_dof


# --- chi^2/dof sanity on single runs -----------------------------------------

@pytest.mark.parametrize("make_ig", [
    lambda: igs.make_cosine(dim=4),
    lambda: igs.make_gaussian(dim=3, sigma=0.2),
    lambda: igs.make_roos_arnold(dim=4),
], ids=["cosine", "gaussian", "roos_arnold"])
def test_chi2_dof_in_sane_band(make_ig):
    """With 15 dof entering the combination, chi^2/dof of a consistent run
    lies in [0.2, 5] (P(chi2_15/15 < 0.2) ~ 3e-4, P(> 5) ~ 1e-10); values
    outside mean the per-iteration sigma2 is wrong, not bad luck."""
    ig = make_ig()
    cfg = VegasConfig(neval=10_000, max_it=18, skip=2, ninc=64, chunk=4096)
    r = run(ig, cfg, key=KEY)
    assert r.n_it == 16
    assert 0.2 <= r.chi2_dof <= 5.0, r


# --- sdev ~ 1/sqrt(neval) ----------------------------------------------------

def test_sdev_scaling_frozen_map_is_sqrt_neval():
    """alpha = beta = 0 AND a pinned ``nstrat`` freeze map and
    stratification geometry: the loop is plain stratified MC on a fixed
    grid, so 4x neval must give exactly 2x smaller combined sdev (measured
    ratios sit within ~0.5% of 2; without pinning nstrat the cube count
    grows with neval and the rate is the BETTER N^(-1/2 - 1/d) stratified
    one — ~4x per 4x here, which is what this test would catch as a
    mis-scaling if it ever leaked into the frozen configuration)."""
    ig = igs.make_gaussian(dim=2, sigma=0.3)
    sdevs = []
    for neval in (4_000, 16_000, 64_000):
        cfg = VegasConfig(neval=neval, max_it=4, skip=0, ninc=32,
                          chunk=4096, alpha=0.0, beta=0.0, nstrat=4)
        sdevs.append(run(ig, cfg, key=KEY).sdev)
    for lo, hi in zip(sdevs[1:], sdevs[:-1]):
        ratio = hi / lo
        assert 1.85 <= ratio <= 2.15, (sdevs, ratio)


def test_sdev_scaling_adaptive_is_monotone():
    """With adaptation on the scaling is SUPER-1/sqrt(neval) (more evals
    also buy a better map), so assert monotone shrinkage plus at least the
    MC floor over the full 16x ladder."""
    ig = igs.make_gaussian(dim=3, sigma=0.2)
    sdevs = []
    for neval in (4_000, 16_000, 64_000):
        cfg = VegasConfig(neval=neval, max_it=8, skip=3, ninc=64,
                          chunk=4096)
        sdevs.append(run(ig, cfg, key=KEY).sdev)
    assert sdevs[0] > sdevs[1] > sdevs[2], sdevs
    assert sdevs[0] / sdevs[2] >= 2.5, sdevs
