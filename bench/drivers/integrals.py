"""Driver ``integrals``: one integral at a time, back to back, through
``repro.core.run`` as a user calls it, each to the configuration's rtol.

Integral ``i`` of a run draws from ``fold_in(seed_key(seed), i)``.  The
window runs integrals until ``seconds`` have passed; the one in flight then
completes and counts.  ``integral_s`` is the window's wall time, from its
start to the end of its last integral, over the integrals it completed.

The check replays, with ``bench/reference.py``, every integral of the
window from its key: the first iteration, on the uniform map and
allocation, and, where the cell's limits name ``iter1_rel``, the first
adapted one, on the map and allocation the reference adapts from its own
first iteration, so nothing the program made goes in.  Each is compared with the program's iteration; so is the
program's combination of the iterations it reported.  An allocation
``floor(neval p)`` can differ by one in a cube where ``neval p`` lies within
f32 rounding of a whole number, which moves the cube boundaries of the
evaluation axis after it: the adapted iteration is compared only where the
reference's allocation keeps clear of that (``FLIP_MARGIN``), and the
iterations after it, where the two runs have drifted apart by a sizeable
share of a standard deviation, are not compared.
"""

from __future__ import annotations

import math
import time

import numpy as np

import reference

#: An adapted iteration is compared only where the reference's allocation
#: for it lies farther than this (relative) from a whole number in every
#: cube: nearer, the program's own rounding may allocate otherwise.
FLIP_MARGIN = 5e-7


def seed_key(jax, seed: int):
    """A key from any whole number (``PRNGKey`` keeps 32 bits only)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Driver:
    def __init__(self, *, config: dict, traffic: dict, limits: dict,
                 seed: int):
        import jax
        from repro.core import VegasConfig
        from repro.core import integrands
        from repro.engine import ExecutionConfig, StopPolicy

        self.jax, self.config, self.limits = jax, config, limits
        execution = ExecutionConfig(backend="auto",
                                    stop=StopPolicy(rtol=config["rtol"]))
        self.cfg = VegasConfig(
            neval=config["neval"], max_it=config["max_it"],
            skip=config["skip"], ninc=config["ninc"], alpha=config["alpha"],
            beta=config["beta"], max_cubes=config["max_cubes"],
            chunk=config["chunk"], dtype=config["dtype"], execution=execution)
        self.integrand = getattr(integrands, "make_" + config["integrand"])(
            **config["args"])
        self.base = seed_key(jax, seed)
        # Iterations replayed for every integral: as many as the limits
        # compare (``iter{k}_rel`` for k below this), the first at least.
        self.replayed = 1 + max([0] + [int(k[4:-4]) for k in limits
                                       if k.startswith("iter")])
        self.done = []          # per integral: key index, results, stats

    def _integral(self, i: int):
        from repro.core import run
        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.integral"):
            r = run(self.integrand, self.cfg,
                    key=jax.random.fold_in(self.base, i))
        with jax.profiler.TraceAnnotation("bench.fetch"):
            results = np.asarray(r.state.results, np.float64)
        return {"i": i, "mean": r.mean, "sdev": r.sdev,
                "n_it": r.n_it_used, "results": results,
                "wall_s": time.perf_counter() - t0}

    def warm_up(self) -> None:
        """One integral off the window's keys: compiles (or fetches from the
        persistent cache) every program the window runs."""
        self._integral(-1 & 0x7FFFFFFF)

    def window(self, seconds: float) -> dict:
        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            i = 0
            while True:
                self.done.append(self._integral(i))
                i += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        rtol = self.config["rtol"]
        failed = sum(1 for d in self.done
                     if not (math.isfinite(d["mean"]) and math.isfinite(d["sdev"])
                             and d["sdev"] <= rtol * abs(d["mean"])))
        iters = sum(d["n_it"] for d in self.done)
        return {"attempted": len(self.done), "failed": failed,
                "integrals": len(self.done), "iterations": iters,
                "evals_requested": iters * self.config["neval"],
                "iterations_each": [d["n_it"] for d in self.done],
                "wall_s": wall, "pull_max": self.pull(),
                "fastest_s": min(d["wall_s"] for d in self.done),
                "slowest_s": max(d["wall_s"] for d in self.done),
                "end_to_end": {"integral_s": wall / len(self.done)}}

    def release(self) -> None:
        """Nothing of the program outlives a call to ``run``: the window
        kept host copies of what it checks."""

    def check(self) -> dict:
        """The numbers compared, each beside its limit."""
        got = self.numbers()
        print(" ".join(f"check {k}={v}" for k, v in got.items()
                       if k.endswith("_compared")), flush=True)
        return {k: {"value": got[k], "limit": v}
                for k, v in self.limits.items()}

    def numbers(self, candidate=None) -> dict:
        """Every number the check can compare, each the worst over the
        integrals: ``iter{k}_rel``, the gap of iteration ``k`` to the
        reference's replay (the larger of the estimate's and the variance's
        relative gaps), and ``combine_rel``, the combination's.  Beside
        them, ``iter{k}_compared`` counts the integrals whose iteration
        ``k`` was compared (every one at k = 0; see ``FLIP_MARGIN``).

        ``candidate`` stands in for the program's integrals (the control
        passes the reference's own, run in a lower precision)."""
        cfg, jax = self.config, self.jax
        done = self.done if candidate is None else candidate
        sz = reference.sizes(cfg)
        step = reference.make_iteration(cfg, sz)
        mu = reference.peak(cfg)
        got = {}
        for k in range(self.replayed):
            got[f"iter{k}_rel"], got[f"iter{k}_compared"] = 0.0, 0
        got["combine_rel"] = 0.0
        for d in done:
            key = jax.random.fold_in(self.base, d["i"])
            n = min(self.replayed, d["n_it"])
            for k, (i_k, s_k, margin) in enumerate(
                    reference.replay(step, sz, key, mu, n)):
                if margin <= FLIP_MARGIN:
                    continue
                p_k, q_k = d["results"][k]
                got[f"iter{k}_rel"] = max(got[f"iter{k}_rel"], _rel(p_k, i_k),
                                          _rel(q_k, s_k))
                got[f"iter{k}_compared"] += 1
            m, s = reference.combine(d["results"][:d["n_it"], 0],
                                     d["results"][:d["n_it"], 1], cfg["skip"])
            got["combine_rel"] = max(got["combine_rel"], _rel(d["mean"], m),
                                     _rel(d["sdev"], s))
        return got

    def pull(self) -> float:
        """The largest |mean - closed form| / sdev of the window: printed,
        not compared (the control gives it no upper reading)."""
        exact = reference.exact_value(self.config)
        return max((abs(d["mean"] - exact) / d["sdev"] if d["sdev"] > 0
                    else math.inf) for d in self.done)

    def control(self, dtype) -> list[dict]:
        """The reference at ``dtype`` in the program's place: as many
        integrals as the window ran (3 without a window), keyed alike."""
        cfg, jax = self.config, self.jax
        sz = reference.sizes(cfg)
        step = reference.make_iteration(cfg, sz, dtype)
        out = []
        for i in range(len(self.done) or 3):
            mean, sdev, n_it, means, sig2 = reference.run(
                cfg, jax.random.fold_in(self.base, i), step, sz,
                reference.peak(cfg), dtype)
            results = np.zeros((cfg["max_it"], 2))
            results[:, 1] = np.inf
            results[:n_it, 0], results[:n_it, 1] = means, sig2
            out.append({"i": i, "mean": mean, "sdev": sdev, "n_it": n_it,
                        "results": results})
        return out


def _rel(a, b) -> float:
    a, b = float(a), float(b)
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)
