"""Adaptive importance-sampling map (the "VEGAS map", Lepage 1978/2021).

The map is a per-dimension piecewise-linear change of variables
``y in [0,1) -> x in [a,b]`` defined by ``ninc`` intervals whose widths adapt
so that each interval contributes equally to ``int |J f|^2``.  cuVegas keeps
the map on-GPU and updates it with a sequential walk (its "updateMap",
Alg. 1); here the update is re-expressed as cumsum + searchsorted + gather,
which is fully parallel on TPU (DESIGN.md C4).

All functions are pure and jit-safe; the map itself is a plain ``(d, ninc+1)``
array of interval edges.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs

# Floor for damped weights: keeps every interval at non-zero width so the
# Jacobian never degenerates (vegas' TINY).
_TINY = 1e-30


def uniform_edges(lower, upper, ninc: int, dtype=jnp.float32) -> jax.Array:
    """Initial map: ``ninc`` equal intervals per dimension.

    lower/upper: (d,) integration bounds. Returns edges (d, ninc+1).
    """
    lower = jnp.asarray(lower, dtype)
    upper = jnp.asarray(upper, dtype)
    t = jnp.linspace(0.0, 1.0, ninc + 1, dtype=dtype)
    return lower[:, None] + (upper - lower)[:, None] * t[None, :]


def apply_map(edges: jax.Array, y: jax.Array):
    """Map uniform points ``y (n, d) in [0,1)`` through the grid.

    Returns ``(x, jac, iy)``:
      x   (n, d) points in the integration volume,
      jac (n,)   product over dims of ``ninc * dx_i`` (eq. (3) of the paper),
      iy  (n, d) int32 interval index per dimension (for weight accumulation).
    """
    ninc = edges.shape[1] - 1
    yn = y * ninc
    iy = jnp.clip(yn.astype(jnp.int32), 0, ninc - 1)
    frac = yn - iy
    # Single-index-array formulation: gather the left edge and the interval
    # width with the SAME indices (one fewer gather; also what the Pallas
    # kernel implements).
    widths = jnp.diff(edges, axis=1)                                  # (d, ninc)
    e_lo = jnp.take_along_axis(edges.T, iy, axis=0, mode="clip")     # (n, d)
    dx = jnp.take_along_axis(widths.T, iy, axis=0, mode="clip")      # (n, d)
    x = e_lo + frac * dx
    # Jacobian in log form. Two reasons: (a) prod(ninc*dx) overflows f32 for
    # strongly adapted high-d maps while the log-sum never does; (b) the
    # gather+reduce-prod fusion miscompiles on XLA:CPU (jax 0.8.2): jit
    # programs containing it produce all-NaN jac while the de-optimized
    # op-by-op execution is clean (confirmed via jax_debug_nans; see
    # DESIGN.md D4 note). The log form sidesteps the bad fusion cluster.
    jac = jnp.exp(jnp.sum(jnp.log(jnp.maximum(ninc * dx, _TINY)), axis=-1))
    return x, jac, iy


def accumulate_map_weights(iy: jax.Array, w2: jax.Array, cnt: jax.Array, ninc: int):
    """Reference accumulation of ``sum (J f)^2`` per (dim, interval).

    iy (n, d) int32, w2 (n,) weights, cnt (n,) 1.0 for live evals / 0.0 for
    masked tail. Returns (sums (d, ninc), counts (d, ninc)). The Pallas kernel
    computes the same contraction as one-hot matmuls on the MXU; this
    scatter-add form is the oracle.
    """
    d = iy.shape[1]
    flat = (jnp.arange(d, dtype=jnp.int32)[None, :] * ninc + iy).reshape(-1)
    sums = jnp.zeros((d * ninc,), w2.dtype).at[flat].add(
        jnp.repeat(w2[:, None], d, axis=1).reshape(-1))
    cnts = jnp.zeros((d * ninc,), w2.dtype).at[flat].add(
        jnp.repeat(cnt[:, None], d, axis=1).reshape(-1))
    return sums.reshape(d, ninc), cnts.reshape(d, ninc)


def _smooth_and_damp(sums: jax.Array, counts: jax.Array, alpha) -> jax.Array:
    """vegas' smoothing + alpha-damping of the accumulated weights.

    sums/counts: (d, ninc). Returns damped weights (d, ninc), >= _TINY.
    """
    avg = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), 0.0)
    # 3-point smoothing with (1,6,1)/8 interior and (7,1)/8 at the ends.
    left = jnp.concatenate([avg[:, :1], avg[:, :-1]], axis=1)
    right = jnp.concatenate([avg[:, 1:], avg[:, -1:]], axis=1)
    sm = (left + 6.0 * avg + right) / 8.0
    total = jnp.sum(sm, axis=1, keepdims=True)
    r = jnp.where(total > 0, sm / jnp.maximum(total, _TINY), 1.0 / sm.shape[1])
    # Damping: w = ((r - 1)/ln r)^alpha, the classic VEGAS compression. r is a
    # normalized distribution so r in [0, 1]; guard the r->0 and r->1 limits.
    r = jnp.clip(r, _TINY, 1.0 - 1e-12)
    w = ((r - 1.0) / jnp.log(r)) ** alpha
    return jnp.maximum(w, _TINY)


def adapt_edges(edges: jax.Array, sums: jax.Array, counts: jax.Array, alpha) -> jax.Array:
    """One map adaptation step (vectorized "updateMap").

    New edges are placed so every new interval holds an equal share of the
    damped weight; realized as piecewise-linear inversion of the cumulative
    weight via searchsorted (parallel; cuVegas does a sequential walk).
    """
    with obs.scope("vegas.adapt_edges"):
        ninc = edges.shape[1] - 1
        w = _smooth_and_damp(sums, counts, alpha)          # (d, ninc)

        def per_dim(edges_d, w_d):
            cum = jnp.concatenate([jnp.zeros((1,), w_d.dtype),
                                   jnp.cumsum(w_d)])
            targets = cum[-1] * jnp.arange(1, ninc, dtype=w_d.dtype) / ninc
            j = jnp.clip(jnp.searchsorted(cum, targets, side="right") - 1,
                         0, ninc - 1)
            frac = (targets - cum[j]) / jnp.maximum(w_d[j], _TINY)
            new_mid = edges_d[j] + frac * (edges_d[j + 1] - edges_d[j])
            new = jnp.concatenate([edges_d[:1], new_mid, edges_d[-1:]])
            # Guard monotonicity against fp round-off in the interpolation.
            return jax.lax.cummax(new, axis=0)

        return jax.vmap(per_dim)(edges, w)
