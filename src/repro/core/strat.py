"""Adaptive stratified sampling (the "+" in VEGAS+).

y-space [0,1)^d is cut into ``nstrat`` equal slices per dimension (a grid of
``nstrat**d`` hypercubes).  Each cube h receives ``n_h`` integrand evaluations,
re-allocated every iteration proportionally to ``d_h**beta`` where d_h is the
cube's variance contribution (paper eq. (5)-(7)).

Shapes must stay static under jit, so the eval axis has a fixed capacity
``n_cap`` and iterations that need fewer evals mask the tail (DESIGN.md C2):
``mapEvalsToCubes`` inverts ``cumsum(n_h)`` with one scatter of cube
boundaries and a prefix sum over the eval axis, and out-of-range evals get
cube id ``n_cubes`` (an overflow bucket that is dropped).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import obs


def choose_nstrat(neval: int, dim: int, max_cubes: int = 1 << 20) -> int:
    """vegas' heuristic: ~(neval/2)^(1/dim) slices/dim, capped by max_cubes."""
    ns = int(math.floor((neval / 2.0) ** (1.0 / dim)))
    ns = max(ns, 1)
    while ns > 1 and ns**dim > max_cubes:
        ns -= 1
    return ns


def eval_capacity(neval: int, n_cubes: int) -> int:
    """Static eval-axis capacity: every cube is guaranteed >= 2 evals, so the
    adapted totals can exceed neval by at most 2 per cube."""
    return neval + 2 * n_cubes


def uniform_nh(neval: int, n_cubes: int) -> jax.Array:
    """Classic-VEGAS / m-CUBES allocation: equal evals per cube (beta = 0)."""
    base = max(neval // n_cubes, 2)
    return jnp.full((n_cubes,), base, dtype=jnp.int32)


def map_evals_to_cubes(n_h: jax.Array, n_cap: int):
    """cuVegas' mapEvalsToCubes, vectorized.

    Returns ``(cube (n_cap,) int32, n_used scalar)``. Evals past the active
    total get cube id ``n_cubes`` (overflow bucket).
    """
    return cubes_for_slice(n_h, 0, n_cap), jnp.sum(n_h)


def cubes_for_slice(n_h: jax.Array, start, length: int):
    """Cube ids for a contiguous slice [start, start+length) of the *global*
    eval axis. ``start`` may be traced (shard-local offsets under shard_map);
    evals past the active total get the overflow id ``n_cubes``.

    The id of eval ``e`` is ``#{h : cumsum(n_h)[h] <= e}``: a 1 marks each
    cube's end on the slice (ends at or before ``start`` pile up at 0, ends
    past the slice are dropped) and the prefix sum of the marks counts them.
    One scatter of ``n_cubes`` sorted indices and one prefix sum of
    ``length`` lanes, so build a fill's whole range once, not per chunk.
    """
    with obs.scope("vegas.cube_ids"):
        pos = jnp.maximum(jnp.cumsum(n_h) - start, 0)
        marks = jnp.zeros((length,), jnp.int32).at[pos].add(
            1, mode="drop", indices_are_sorted=True)
        return jnp.cumsum(marks).astype(jnp.int32)


def cube_coords(cube: jax.Array, nstrat: int, dim: int) -> jax.Array:
    """Decode cube ids (n,) into per-dimension stratification coords (n, dim)."""
    pows = nstrat ** jnp.arange(dim, dtype=jnp.int64 if nstrat**dim > 2**31 else jnp.int32)
    return (cube[:, None] // pows[None, :]) % nstrat


def stratified_y(cube: jax.Array, u: jax.Array, nstrat: int) -> jax.Array:
    """Uniform u (n, d) -> stratified y (n, d): offset into the cube's cell."""
    coords = cube_coords(cube, nstrat, u.shape[1]).astype(u.dtype)
    return (coords + u) / nstrat


def adapt_nh(d_h: jax.Array, beta, neval: int, n_min: int = 2) -> jax.Array:
    """Re-allocate evals per cube: n_h = max(n_min, floor(neval * p_h)) with
    p_h = d_h^beta / sum d_h^beta (paper's damped stratification update)."""
    with obs.scope("vegas.adapt_nh"):
        d_h = jnp.maximum(d_h, 0.0)
        p = d_h ** beta
        tot = jnp.sum(p)
        # A total at or under the clamp carries no signal: normalizing by
        # the clamp instead of the total would drop sum(n_h) below
        # neval - n_cubes.
        p = jnp.where(tot > 1e-30, p / jnp.maximum(tot, 1e-30),
                      1.0 / d_h.shape[0])
        return jnp.maximum(jnp.floor(neval * p), n_min).astype(jnp.int32)
