"""Plain VEGAS+ (Lepage 2021; cuVegas, arXiv:2408.09229, Alg. 1-2) in jax.numpy.

The benchmark's yardstick for `correct`.  It imports nothing of the system
under test: the stratification, the sample stream, the map, the estimate,
the two adaptations and the combination are written out here from the
published algorithm, with the sample stream keyed as the system documents
it (iteration ``it`` draws from ``fold_in(key, it)``, chunk ``g`` of the
flat evaluation axis from ``fold_in(·, g)``), so that one iteration of the
system and of this reference see the same points.

``dtype`` is the precision of the sample path (uniforms excepted: they are
always drawn in float32, as the stream is defined): the gathered edges and
widths, the point ``x``, the Jacobian and the integrand.  Sums accumulate in
float32.  ``float32`` is the configuration's precision; ``bfloat16`` is the
control (`PERF.md`, "How correct is decided").
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

TINY = 1e-30


# --- integrands, from the paper's Table 3 ------------------------------------

def integrand_fn(cfg: dict, dtype):
    """``f(x (n, d), mu) -> (n,)`` of the configuration's integrand; ``mu``,
    the Gaussian's peak position, is an argument so that one compiled
    program serves every position."""
    name, a = cfg["integrand"], cfg["args"]
    d = a["dim"]
    if name == "gaussian":
        sigma = a["sigma"]
        norm = 1.0 / (2.0 * math.pi * sigma ** 2) ** (d / 2.0)

        def f(x, mu):
            r2 = jnp.sum((x - mu.astype(dtype)) ** 2, axis=-1)
            return jnp.asarray(norm, dtype) * jnp.exp(
                -r2 / jnp.asarray(2.0 * sigma ** 2, dtype))
        return f
    if name == "roos_arnold":
        return lambda x, mu: jnp.prod(jnp.abs(4.0 * x - 2.0), axis=-1)
    raise ValueError(f"no reference for integrand {name!r}")


def exact_value(cfg: dict, mu: float | None = None) -> float:
    """The integral over the unit cube, in closed form (float64)."""
    name, a = cfg["integrand"], cfg["args"]
    d = a["dim"]
    if name == "gaussian":
        mu = a["mu"] if mu is None else mu
        s = a["sigma"] * math.sqrt(2.0)
        return (0.5 * (math.erf((1.0 - mu) / s) + math.erf(mu / s))) ** d
    if name == "roos_arnold":
        return 1.0
    raise ValueError(f"no closed form for integrand {name!r}")


# --- sizes ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sizes:
    dim: int
    neval: int
    ninc: int
    nstrat: int
    n_cubes: int
    n_cap: int
    chunk: int


def sizes(cfg: dict) -> Sizes:
    """vegas' stratification heuristic, ~(neval/2)^(1/d) slices a side,
    capped at ``max_cubes``; every cube keeps at least 2 evaluations, so the
    evaluation axis holds ``neval + 2 n_cubes``, padded to whole chunks."""
    d = cfg["args"]["dim"]
    neval = cfg["neval"]
    ns = max(int(math.floor((neval / 2.0) ** (1.0 / d))), 1)
    while ns > 1 and ns ** d > cfg["max_cubes"]:
        ns -= 1
    n_cubes = ns ** d
    n_cap = neval + 2 * n_cubes
    chunk = min(cfg["chunk"], max(n_cap, 256))
    n_cap = -(-n_cap // chunk) * chunk
    return Sizes(d, neval, cfg["ninc"], ns, n_cubes, n_cap, chunk)


# --- one iteration ---------------------------------------------------------------

def fill(edges, n_h, key_it, f, sz: Sizes, dtype, mu):
    """Sample, map, evaluate and accumulate one iteration.

    Returns ``(map_sums, map_counts, cube_s1, cube_s2)``: per map interval
    the sum of (J f)^2 and the number of samples, per hypercube the sums of
    J f and (J f)^2."""
    d, ninc, ns, nc = sz.dim, sz.ninc, sz.nstrat, sz.n_cubes
    cum = jnp.cumsum(n_h)
    pows = jnp.asarray([ns ** j for j in range(d)], jnp.int32)
    e_lo = edges[:, :-1].astype(dtype)                    # (d, ninc)
    width = (edges[:, 1:] - edges[:, :-1]).astype(dtype)  # (d, ninc)
    dims = jnp.arange(d)[None, :]

    def chunk(acc, g):
        u = jax.random.uniform(jax.random.fold_in(key_it, g), (sz.chunk, d),
                               jnp.float32)
        e = g * sz.chunk + jnp.arange(sz.chunk, dtype=cum.dtype)
        cube = jnp.searchsorted(cum, e, side="right").astype(jnp.int32)
        live = cube < nc
        coords = (jnp.minimum(cube, nc - 1)[:, None] // pows) % ns
        y = (coords.astype(jnp.float32) + u) / ns
        yn = y * ninc
        iy = jnp.clip(yn.astype(jnp.int32), 0, ninc - 1)
        frac = (yn - iy).astype(dtype)
        dx = width[dims, iy]
        x = e_lo[dims, iy] + frac * dx
        jac = jnp.prod(ninc * dx, axis=-1)
        w = jnp.where(live, (jac * f(x, mu)).astype(jnp.float32), 0.0)
        w2 = w * w
        ms, mc, s1, s2 = acc
        flat = (dims * ninc + iy).reshape(-1)
        ms = ms.at[flat].add(jnp.repeat(w2, d))
        mc = mc.at[flat].add(jnp.repeat(live.astype(jnp.float32), d))
        s1 = s1.at[cube].add(w, mode="drop")
        s2 = s2.at[cube].add(w2, mode="drop")
        return (ms, mc, s1, s2), None

    zero = (jnp.zeros((d * ninc,)), jnp.zeros((d * ninc,)),
            jnp.zeros((nc,)), jnp.zeros((nc,)))
    (ms, mc, s1, s2), _ = jax.lax.scan(chunk, zero,
                                       jnp.arange(sz.n_cap // sz.chunk))
    return ms.reshape(d, ninc), mc.reshape(d, ninc), s1, s2


def estimate(s1, s2, n_h):
    """Stratified estimate of the integral, its variance, and each cube's
    standard deviation (the allocation signal)."""
    nh = jnp.maximum(n_h.astype(jnp.float32), 1.0)
    m, q = s1 / nh, s2 / nh
    var = jnp.maximum(q - m * m, 0.0)
    v = 1.0 / n_h.shape[0]
    return (v * jnp.sum(m), v * v * jnp.sum(var / jnp.maximum(nh - 1.0, 1.0)),
            jnp.sqrt(var))


def adapt_nh(sd, beta, neval):
    """n_h = max(2, floor(neval · sd_h^beta / sum sd^beta)), and the
    allocation's margin: the least relative distance of ``neval p_h`` to a
    whole number, over the cubes where the floor and not the minimum of 2
    decides.  A run whose rounding of ``p`` differs from this one's by more
    than the margin may give a cube one evaluation more or fewer, which
    moves the boundaries of every later cube on the evaluation axis."""
    p = jnp.maximum(sd, 0.0) ** beta
    tot = jnp.sum(p)
    p = jnp.where(tot > TINY, p / jnp.maximum(tot, TINY), 1.0 / sd.shape[0])
    x = neval * p
    near = jnp.abs(x - jnp.round(x)) / jnp.maximum(x, 1.0)
    margin = jnp.min(jnp.where(jnp.round(x) >= 3, near, jnp.inf))
    return jnp.maximum(jnp.floor(x), 2).astype(jnp.int32), margin


def adapt_edges(edges, sums, counts, alpha):
    """vegas' map update: average (J f)^2 per interval, smooth (1,6,1)/8,
    normalize, compress ((r-1)/ln r)^alpha, then place the new edges so each
    interval holds an equal share of the compressed weight."""
    ninc = edges.shape[1] - 1
    avg = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), 0.0)
    left = jnp.concatenate([avg[:, :1], avg[:, :-1]], axis=1)
    right = jnp.concatenate([avg[:, 1:], avg[:, -1:]], axis=1)
    sm = (left + 6.0 * avg + right) / 8.0
    tot = jnp.sum(sm, axis=1, keepdims=True)
    r = jnp.where(tot > 0, sm / jnp.maximum(tot, TINY), 1.0 / ninc)
    r = jnp.clip(r, TINY, 1.0 - 1e-12)
    w = jnp.maximum(((r - 1.0) / jnp.log(r)) ** alpha, TINY)

    def one(e, wd):
        cum = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(wd)])
        t = cum[-1] * jnp.arange(1, ninc, dtype=jnp.float32) / ninc
        j = jnp.clip(jnp.searchsorted(cum, t, side="right") - 1, 0, ninc - 1)
        frac = (t - cum[j]) / jnp.maximum(wd[j], TINY)
        mid = e[j] + frac * (e[j + 1] - e[j])
        return jax.lax.cummax(jnp.concatenate([e[:1], mid, e[-1:]]))

    return jax.vmap(one)(edges, w)


def uniform_edges(sz: Sizes):
    t = jnp.linspace(0.0, 1.0, sz.ninc + 1, dtype=jnp.float32)
    return jnp.broadcast_to(t, (sz.dim, sz.ninc + 1))


def uniform_nh(sz: Sizes):
    return jnp.full((sz.n_cubes,), max(sz.neval // sz.n_cubes, 2), jnp.int32)


# --- runs ------------------------------------------------------------------------

def make_iteration(cfg: dict, sz: Sizes, dtype=jnp.float32):
    """``step(edges, n_h, key, it, mu) -> (I, sigma2, edges', n_h',
    margin')``, jitted once for every key, iteration and peak position."""
    f = integrand_fn(cfg, dtype)

    @jax.jit
    def step(edges, n_h, key, it, mu):
        ms, mc, s1, s2 = fill(edges, n_h, jax.random.fold_in(key, it), f, sz,
                              dtype, mu)
        i_it, sig2, sd = estimate(s1, s2, n_h)
        return (i_it, sig2, adapt_edges(edges, ms, mc, cfg["alpha"]),
                *adapt_nh(sd, cfg["beta"], sz.neval))
    return step


def peak(cfg: dict, mu: float | None = None):
    """The Gaussian's peak position as the step takes it (0 where the
    integrand has none)."""
    return jnp.float32(cfg["args"].get("mu", 0.0) if mu is None else mu)


def replay(step, sz: Sizes, key, mu, n: int):
    """``[(I_k, sigma2_k, margin_k) for k < n]`` of a run from the uniform
    map and allocation, adapting both after each iteration as the run does;
    ``margin_k`` is that of the allocation iteration ``k`` ran on (infinite
    for the uniform one)."""
    edges, n_h, margin = uniform_edges(sz), uniform_nh(sz), math.inf
    out = []
    for it in range(n):
        i_it, s_it, edges, n_h, next_margin = step(edges, n_h, key, it, mu)
        out.append((float(i_it), float(s_it), margin))
        margin = float(next_margin)
    return out


def combine(means, sig2, skip: int):
    """Inverse-variance combination of iterations ``skip..`` in float64:
    ``(mean, sdev)``; ``(0, inf)`` when none is usable."""
    m = np.asarray(means, np.float64)[skip:]
    s = np.asarray(sig2, np.float64)[skip:]
    use = np.isfinite(s) & (s > 0)
    if not use.any():
        return 0.0, math.inf
    w = 1.0 / s[use]
    return float((w * m[use]).sum() / w.sum()), float(1.0 / math.sqrt(w.sum()))


def combine_in(means, sig2, skip: int, dtype):
    """:func:`combine` computed in ``dtype``: the control's combination."""
    m = jnp.asarray(means[skip:], dtype)
    w = 1.0 / jnp.asarray(sig2[skip:], dtype)
    tot = jnp.sum(w)
    return float(jnp.sum(w * m) / tot), float(1.0 / jnp.sqrt(tot))


def run(cfg: dict, key, step, sz: Sizes, mu, dtype=jnp.float32):
    """A whole VEGAS+ run to ``rtol`` (at most ``max_it`` iterations, never
    before ``min_it`` = 2) with ``step`` from :func:`make_iteration`:
    ``(mean, sdev, n_it, iteration means, sigma2)``.  Used in the program's
    place by the control, which also combines in ``dtype``."""
    edges, n_h = uniform_edges(sz), uniform_nh(sz)
    means, sig2 = [], []
    for it in range(cfg["max_it"]):
        i_it, s_it, edges, n_h, _ = step(edges, n_h, key, it, mu)
        means.append(float(i_it))
        sig2.append(float(s_it))
        mean, sdev = combine(means, sig2, cfg["skip"])
        if it + 1 >= 2 and sdev <= cfg["rtol"] * abs(mean):
            break
    if dtype != jnp.float32:
        mean, sdev = combine_in(means, sig2, cfg["skip"], dtype)
    return mean, sdev, len(means), means, sig2
