"""Parameterized integrand families: the batch axis of the batched engine.

An :class:`IntegrandFamily` is a single traced callable ``fn(params, x)``
plus a pytree of per-scenario parameters whose leaves carry a leading batch
axis ``B``.  The engine ``vmap``s the whole VEGAS+ iteration loop over that
axis (DESIGN.md B2), so B scenarios — e.g. Gaussian peaks at B locations, an
Asian option at B strikes, B ridge orientations — adapt and integrate
concurrently inside one XLA program.

Bounds are shared across the batch (they fix the static map geometry); only
``params`` varies per scenario.  ``instance(b)`` materializes scenario ``b``
as a plain :class:`~repro.core.integrands.Integrand` for serial comparison
runs and tests.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.integrands import Integrand, cumsum_cols


@dataclasses.dataclass(frozen=True)
class IntegrandFamily:
    name: str
    dim: int
    fn: Callable[[Any, jax.Array], jax.Array]  # fn(params, x (n,d)) -> (n,)
    lower: tuple
    upper: tuple
    params: Any                      # pytree; every leaf has leading axis B
    targets: np.ndarray | None = None  # (B,) analytic values where known

    @property
    def batch_size(self) -> int:
        return jax.tree.leaves(self.params)[0].shape[0]

    def bind(self, params) -> Integrand:
        """Close over one (possibly traced) parameter slice — the integrand
        the vmapped loop evaluates."""
        return Integrand(self.name, self.dim, lambda x: self.fn(params, x),
                         self.lower, self.upper)

    def instance(self, b: int) -> Integrand:
        """Scenario ``b`` as a standalone Integrand (serial runs, tests)."""
        p = jax.tree.map(lambda leaf: leaf[b], self.params)
        target = float(self.targets[b]) if self.targets is not None else None
        return Integrand(f"{self.name}[{b}]", self.dim,
                         lambda x: self.fn(p, x), self.lower, self.upper,
                         target)


# --- Concrete families --------------------------------------------------------

def make_gaussian_family(mus, dim: int = 4, sigma: float = 0.1) -> IntegrandFamily:
    """Product Gaussians peaked at per-scenario locations ``mus (B,)`` (the
    paper's Table 3 #7 with the peak swept across the unit cube)."""
    mus = np.asarray(mus, np.float64)
    norm = 1.0 / (2.0 * math.pi * sigma**2) ** (dim / 2.0)

    def fn(mu, x):
        return norm * jnp.exp(-jnp.sum((x - mu) ** 2, axis=-1) / (2.0 * sigma**2))

    targets = np.array([
        (math.erf((1.0 - m) / (sigma * math.sqrt(2.0))) / 2.0
         + math.erf(m / (sigma * math.sqrt(2.0))) / 2.0) ** dim
        for m in mus])
    return IntegrandFamily("gaussian_family", dim, fn, (0.0,) * dim,
                           (1.0,) * dim, jnp.asarray(mus, jnp.float32), targets)


def make_asian_family(strikes, n_steps: int = 8, s0: float = 100.0,
                      r: float = 0.1, sigma: float = 0.2, t_mat: float = 1.0,
                      geometric: bool = True) -> IntegrandFamily:
    """Asian call (paper eq. (10)-(11)) at per-scenario strikes ``(B,)`` —
    the serving-shaped workload: one adapted map family, many contracts.
    The geometric variant has a closed form used as the target."""
    strikes = np.asarray(strikes, np.float64)
    dt = t_mat / n_steps
    drift = (r - 0.5 * sigma**2) * dt
    vol = sigma * math.sqrt(dt)

    def fn(strike, x):
        eps = 1e-6 if x.dtype == jnp.float32 else 1e-12
        xc = jnp.clip(x, eps, 1.0 - eps)
        z = jax.scipy.special.erfinv(2.0 * xc - 1.0) * math.sqrt(2.0)
        logpath = cumsum_cols(drift + vol * z)
        if geometric:
            avg = s0 * jnp.exp(jnp.mean(logpath, axis=-1))
        else:
            avg = jnp.mean(s0 * jnp.exp(logpath), axis=-1)
        return math.exp(-r * t_mat) * jnp.maximum(avg - strike, 0.0)

    targets = None
    if geometric:
        from repro.core.targets import asian_geometric_closed_form
        targets = np.array([asian_geometric_closed_form(s0, k, r, sigma,
                                                        t_mat, n_steps)
                            for k in strikes])
    name = "asian_geo_family" if geometric else "asian_family"
    return IntegrandFamily(name, n_steps, fn, (0.0,) * n_steps,
                           (1.0,) * n_steps,
                           jnp.asarray(strikes, jnp.float32), targets)


def make_asian_greeks_family(strikes, sigmas=None, n_steps: int = 8,
                             s0: float = 100.0, r: float = 0.1,
                             t_mat: float = 1.0) -> IntegrandFamily:
    """Geometric Asian call with per-scenario ``{'strike', 'sigma'}`` params
    — the Greeks workload of the differentiable engine (`repro.grad`, §11).

    Where `make_asian_family` bakes the volatility into the closure (a
    static float the tracer never sees), here BOTH contract parameters ride
    the params pytree, so ``d(price)/d(strike)`` (dual delta) and
    ``d(price)/d(sigma)`` (vega) flow out of one vjp per scenario.  The
    drift/vol path coefficients are recomputed from the traced ``sigma``
    inside ``fn`` — that dependence IS the vega path.  Targets stay the
    geometric closed form, so grad tests can finite-difference an exact
    price curve rather than another Monte Carlo estimate.
    """
    strikes = np.asarray(strikes, np.float64)
    sigmas = (np.full_like(strikes, 0.2) if sigmas is None
              else np.broadcast_to(np.asarray(sigmas, np.float64),
                                   strikes.shape))
    dt = t_mat / n_steps

    def fn(params, x):
        strike, sigma = params["strike"], params["sigma"]
        drift = (r - 0.5 * sigma**2) * dt
        vol = sigma * math.sqrt(dt)
        eps = 1e-6 if x.dtype == jnp.float32 else 1e-12
        xc = jnp.clip(x, eps, 1.0 - eps)
        z = jax.scipy.special.erfinv(2.0 * xc - 1.0) * math.sqrt(2.0)
        logpath = cumsum_cols(drift + vol * z)
        avg = s0 * jnp.exp(jnp.mean(logpath, axis=-1))
        return math.exp(-r * t_mat) * jnp.maximum(avg - strike, 0.0)

    from repro.core.targets import asian_geometric_closed_form
    targets = np.array([asian_geometric_closed_form(s0, k, r, sig, t_mat,
                                                    n_steps)
                        for k, sig in zip(strikes, sigmas)])
    params = {"strike": jnp.asarray(strikes, jnp.float32),
              "sigma": jnp.asarray(sigmas, jnp.float32)}
    return IntegrandFamily("asian_greeks_family", n_steps, fn,
                           (0.0,) * n_steps, (1.0,) * n_steps, params,
                           targets)


def make_ridge_family(directions, dim: int = 4, n_peaks: int = 50) -> IntegrandFamily:
    """Ridge integrand (Table 3 #8) with per-scenario peak-line orientation.

    ``directions (B, dim)`` with components in (0, 1]: scenario b places its
    ``n_peaks`` Gaussians at ``c_i * directions[b]`` for ``c_i`` on a uniform
    grid in [0, 1] — direction (1,...,1) recovers the paper's main-diagonal
    ridge.  The target factorizes per dimension (erf closed form), so every
    orientation keeps an analytic value.
    """
    directions = np.asarray(directions, np.float64)
    assert directions.shape[1] == dim, (directions.shape, dim)
    centers = np.linspace(0.0, 1.0, n_peaks)
    scale = 10000.0 / (math.pi**2 * n_peaks)
    cj = jnp.asarray(centers, jnp.float32)

    def fn(v, x):
        # (n, 1, d) - (P, d) peak grid along direction v.
        peaks = cj[:, None] * v[None, :]
        d2 = jnp.sum((x[:, None, :] - peaks[None, :, :]) ** 2, axis=-1)
        return scale * jnp.sum(jnp.exp(-100.0 * d2), axis=-1)

    from scipy.special import erf
    # per-(peak, dim) marginal: int_0^1 exp(-100 (x - c v_j)^2) dx
    cv = centers[:, None] * directions[:, None, :]          # (B, P, d)
    per = (math.sqrt(math.pi) / 20.0) * (erf(10.0 * (1.0 - cv)) + erf(10.0 * cv))
    targets = scale * np.sum(np.prod(per, axis=-1), axis=-1)  # (B,)
    return IntegrandFamily("ridge_family", dim, fn, (0.0,) * dim,
                           (1.0,) * dim,
                           jnp.asarray(directions, jnp.float32), targets)


FAMILIES = {
    "gaussian": lambda b: make_gaussian_family(np.linspace(0.2, 0.8, b)),
    "asian": lambda b: make_asian_family(np.linspace(80.0, 120.0, b)),
    "asian_greeks": lambda b: make_asian_greeks_family(
        np.linspace(80.0, 120.0, b), np.linspace(0.15, 0.3, b)),
    "ridge": lambda b: make_ridge_family(
        0.5 + 0.5 * (np.arange(b)[:, None] * np.arange(1, 5)[None, :] % 7) / 7.0),
}
