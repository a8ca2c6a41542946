"""Test integrands (paper Table 3) plus the two application integrands
(Asian option, eq. (10)-(11); Feynman path integral, eq. (12)-(13)).

Every integrand is a pure function ``f(x) -> (n,)`` over a batch ``x (n, d)``
and carries its integration bounds and dimension via :class:`Integrand`.
These are traced into the Pallas fill kernel at compile time — the JAX
analogue of cuVegas' Numba-compiled device functions (DESIGN.md C7).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Integrand:
    name: str
    dim: int
    fn: Callable[[jax.Array], jax.Array]
    lower: tuple
    upper: tuple
    target: float | None = None  # analytic value of the integral, if known

    def __call__(self, x):
        return self.fn(x)


def _unit(name, dim, fn, target):
    return Integrand(name, dim, fn, (0.0,) * dim, (1.0,) * dim, target)


# Mosaic (the TPU kernel compiler) lowers no ``reduce_prod`` and no
# ``cumsum``, and every integrand here is traced into the fill kernel as well
# as into the ``ref`` oracle.  Both scans therefore run unrolled over the
# static ``d`` columns, in the same left-to-right order on every backend.

def prod_cols(v):
    """Product over the last axis of ``v (n, d)`` -> ``(n,)``."""
    out = v[:, 0:1]
    for k in range(1, v.shape[-1]):
        out = out * v[:, k:k + 1]
    return out[:, 0]


def cumsum_cols(v):
    """Running sum over the last axis of ``v (n, d)`` -> ``(n, d)``."""
    cols = [v[:, 0:1]]
    for k in range(1, v.shape[-1]):
        cols.append(cols[-1] + v[:, k:k + 1])
    return jnp.concatenate(cols, axis=-1)


# --- Table 3 -----------------------------------------------------------------

def make_sine_exp():
    # (1) f = sin(x1) + exp(x2), 2D. Integral = (1 - cos 1) + (e - 1).
    target = (1.0 - math.cos(1.0)) + (math.e - 1.0)
    return _unit("sine_exp", 2, lambda x: jnp.sin(x[:, 0]) + jnp.exp(x[:, 1]), target)


def make_linear(dim=10):
    # (2) f = sum x_i. Integral = d/2.
    return _unit("linear", dim, lambda x: jnp.sum(x, axis=-1), dim / 2.0)


def make_cosine(dim=10):
    # (3) f = prod cos(x_i). Integral = sin(1)^d.
    return _unit("cosine", dim, lambda x: prod_cols(jnp.cos(x)),
                 math.sin(1.0) ** dim)


def make_exponential(dim=10):
    # (4) f = exp(sum x_i^2). Integral = (sqrt(pi)/2 * erfi(1))^d.
    from scipy.special import erfi  # target only; not traced
    target = float((math.sqrt(math.pi) / 2.0 * erfi(1.0)) ** dim)
    return _unit("exponential", dim,
                 lambda x: jnp.exp(jnp.sum(x * x, axis=-1)), target)


def make_roos_arnold(dim=10):
    # (5) f = prod |4 x_i - 2|. Integral = 1.
    return _unit("roos_arnold", dim,
                 lambda x: prod_cols(jnp.abs(4.0 * x - 2.0)), 1.0)


def make_morokoff_caflisch(dim=8):
    # (6) f = (1 + 1/d)^d prod x_i^(1/d). Integral = 1.
    c = (1.0 + 1.0 / dim) ** dim

    def fn(x):
        # x^(1/d) via exp/log with a 0-guard (x=0 has measure zero).
        return c * jnp.exp(jnp.sum(jnp.log(jnp.maximum(x, 1e-30)), axis=-1) / dim)

    return _unit("morokoff_caflisch", dim, fn, 1.0)


def make_gaussian(dim=4, mu=0.5, sigma=0.01):
    # (7) sharply peaked product Gaussian. Integral = prod_i erf-window ~= 1.
    norm = 1.0 / (2.0 * math.pi * sigma**2) ** (dim / 2.0)
    target = float(math.erf((1.0 - mu) / (sigma * math.sqrt(2.0))) / 2.0
                   + math.erf(mu / (sigma * math.sqrt(2.0))) / 2.0) ** dim

    def fn(x):
        return norm * jnp.exp(-jnp.sum((x - mu) ** 2, axis=-1) / (2.0 * sigma**2))

    return _unit("gaussian", dim, fn, target)


def make_ridge(dim=4, n_peaks=1000):
    # (8) "Ridge": sum of n_peaks Gaussians centred along the main diagonal —
    # the computationally intensive, diagonal-structured integrand VEGAS+'s
    # stratification was designed for.
    centers = jnp.linspace(0.0, 1.0, n_peaks)
    scale = 10000.0 / (math.pi**2 * n_peaks)

    def fn(x):
        # (n, 1, d) - (P,) broadcast over the shared diagonal center.
        d2 = jnp.sum((x[:, None, :] - centers[None, :, None]) ** 2, axis=-1)
        return scale * jnp.sum(jnp.exp(-100.0 * d2), axis=-1)

    # target: sum_i prod_j int_0^1 exp(-100 (x - c_i)^2) dx, per-dim closed form.
    c = jnp.asarray(centers, jnp.float64) if jax.config.jax_enable_x64 else centers
    import numpy as np
    from scipy.special import erf
    cn = np.linspace(0.0, 1.0, n_peaks)
    per_dim = (math.sqrt(math.pi) / 20.0) * (erf(10.0 * (1.0 - cn)) + erf(10.0 * cn))
    target = float(scale * np.sum(per_dim**dim))
    return _unit(f"ridge", dim, fn, target)


# --- Applications ------------------------------------------------------------

def make_asian_option(n_steps=16, s0=100.0, strike=100.0, r=0.1, sigma=0.2,
                      t_mat=1.0, geometric=False):
    """Arithmetic(default)/geometric Asian call (paper eq. (10)-(11)).

    d = n_steps uniforms are mapped to standard normals via the inverse-erf,
    driving a discretized GBM path; payoff is discounted average-vs-strike.
    The geometric variant has a Black-Scholes-type closed form used as the
    validation target (targets.asian_geometric_closed_form).
    """
    dt = t_mat / n_steps
    drift = (r - 0.5 * sigma**2) * dt
    vol = sigma * math.sqrt(dt)

    def fn(x):
        # Clamp away from {0,1}: erfinv is singular there (measure zero).
        # The bound must survive float32 rounding (1 - 1e-7 rounds to 1.0f).
        eps = 1e-6 if x.dtype == jnp.float32 else 1e-12
        xc = jnp.clip(x, eps, 1.0 - eps)
        z = jax.scipy.special.erfinv(2.0 * xc - 1.0) * math.sqrt(2.0)
        logret = drift + vol * z                       # (n, d) per-step log-returns
        logpath = cumsum_cols(logret)                  # (n, d) log S_k/S0
        if geometric:
            avg = s0 * jnp.exp(jnp.mean(logpath, axis=-1))
        else:
            avg = jnp.mean(s0 * jnp.exp(logpath), axis=-1)
        return math.exp(-r * t_mat) * jnp.maximum(avg - strike, 0.0)

    name = "asian_geo" if geometric else "asian"
    from .targets import asian_geometric_closed_form
    target = asian_geometric_closed_form(s0, strike, r, sigma, t_mat, n_steps) \
        if geometric else None
    return Integrand(name, n_steps, fn, (0.0,) * n_steps, (1.0,) * n_steps, target)


def make_feynman_path(n_slices=9, t_total=4.0, mass=1.0, x_end=0.0, box=5.0):
    """Harmonic-oscillator lattice path integral <x|e^{-HT}|x> (eq. (12)-(13)).

    d = N-1 interior points; V(x) = x^2/2. The lattice action is a quadratic
    form, so the (untruncated) integral is Gaussian-exact:
    A (2 pi)^{(N-1)/2} / sqrt(det M) — used as target.
    """
    n = n_slices
    dim = n - 1
    a = t_total / n
    amp = (mass / (2.0 * math.pi * a)) ** (n / 2.0)

    def fn(x):
        xp = jnp.pad(x, ((0, 0), (1, 1)), constant_values=x_end)  # endpoints
        kin = (mass / (2.0 * a)) * jnp.sum((xp[:, 1:] - xp[:, :-1]) ** 2, axis=-1)
        pot = a * jnp.sum(0.5 * xp[:, :-1] ** 2, axis=-1)  # j = 0..N-1
        return amp * jnp.exp(-(kin + pot))

    import numpy as np
    k = 2.0 * np.eye(dim) - np.eye(dim, k=1) - np.eye(dim, k=-1)
    m_mat = (mass / a) * k + a * np.eye(dim)  # + aV''-> a for V = x^2/2
    target = float(amp * (2.0 * math.pi) ** (dim / 2.0)
                   / math.sqrt(np.linalg.det(m_mat)))
    return Integrand("feynman_path", dim, fn, (-box,) * dim, (box,) * dim, target)


TABLE3 = {
    1: make_sine_exp,
    2: make_linear,
    3: make_cosine,
    4: make_exponential,
    5: make_roos_arnold,
    6: make_morokoff_caflisch,
    7: make_gaussian,
    8: make_ridge,
}


def table3_suite(ridge_peaks=1000):
    """The seven benchmark integrands of §4.3 (1-7; Ridge excluded there) plus
    Ridge for the breakdown/stratification experiments."""
    return [make_sine_exp(), make_linear(), make_cosine(), make_exponential(),
            make_roos_arnold(), make_morokoff_caflisch(), make_gaussian(),
            make_ridge(n_peaks=ridge_peaks)]
