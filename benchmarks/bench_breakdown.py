"""Paper Table 1: running-time breakdown of the algorithm sections
(init / map+fill / update / results) for an easy (Roos&Arnold) and an
intensive (Ridge) integrand, across n_eval scales.

cuVegas' finding: fill dominates (36-99%) and grows with n_eval; everything
else amortizes.  Same decomposition measured on the JAX engine.

This module also carries the fill perf trajectory (DESIGN.md §7): the
``.../fill_pallas`` vs ``.../fill_fused`` rows time the P-V2 baseline kernel
against the P-V3 streaming kernel at the smoke shapes, and ``.../fill_gpu``
adds the Triton-lowered scatter kernel (DESIGN.md §14) — the numbers behind
BENCH_fill.json, the CI bench gate (``benchmarks.run --gate-fill``) and the
absolute trajectory gate (``--gate-abs``).  The pallas comparison uses
closure-free integrands only: a traced integrand that captures arrays
(e.g. ridge's peak table) cannot be inlined into a pallas kernel body.

The ``table1/phases/*`` rows decompose one fill into its phases so the
accumulation rewrite is attributable per backend without real-GPU access:
``rng`` (chunk-keyed uniform generation), ``eval`` (transform + integrand),
and ``adapt`` (map + stratification update) are measured directly and are
backend-independent at the JAX level; ``accumulate/<backend>`` is measured
directly for ``ref`` (the scatter-add program) and derived as
``total - rng - eval`` for the pallas backends, whose accumulation happens
inside the kernel and cannot be timed in isolation.
"""

from __future__ import annotations

import functools
import time

import jax

from repro import kernels
from repro.core import integrator as I
from repro.core import fill as F
from repro.core import map as vmap_
from repro.core import strat
from repro.core.integrands import make_cosine, make_ridge, make_roos_arnold
from .common import emit, timeit


def _sections(ig, neval):
    cfg = I.VegasConfig(neval=neval, max_it=4, ninc=1024,
                        chunk=min(neval, 1 << 14)).resolve(ig.dim)
    t0 = time.perf_counter()
    state = I.init_state(ig, cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(state.edges)
    t_init = time.perf_counter() - t0

    fill_j = jax.jit(functools.partial(
        F.fill_reference, integrand=ig, nstrat=cfg.nstrat, n_cap=cfg.n_cap,
        chunk=cfg.chunk))
    key = jax.random.fold_in(state.key, 0)
    res = jax.block_until_ready(fill_j(state.edges, state.n_h, key))  # compile
    t0 = time.perf_counter()
    res = jax.block_until_ready(fill_j(state.edges, state.n_h, key))
    t_fill = time.perf_counter() - t0

    upd_j = jax.jit(lambda e, r, d: (
        vmap_.adapt_edges(e, r.map_sums, r.map_counts, 0.5),
        strat.adapt_nh(d, 0.75, cfg.neval)))
    _, _, d_h = F.estimate_from_cubes(res, state.n_h)
    jax.block_until_ready(upd_j(state.edges, res, d_h))
    t0 = time.perf_counter()
    jax.block_until_ready(upd_j(state.edges, res, d_h))
    t_update = time.perf_counter() - t0

    res_j = jax.jit(lambda r, nh: F.estimate_from_cubes(r, nh)[:2])
    jax.block_until_ready(res_j(res, state.n_h))
    t0 = time.perf_counter()
    jax.block_until_ready(res_j(res, state.n_h))
    t_results = time.perf_counter() - t0

    total = t_init + t_fill + t_update + t_results
    return dict(init=t_init, fill=t_fill, update=t_update, results=t_results,
                total=total)


def _fill_backends(ig, neval, ninc=1024):
    """Time the fill implementations on identical (edges, n_h, key):
    reference, pallas baseline (P-V2), pallas fused (P-V3), pallas-gpu
    (Triton scatter).  Tiles/blocks come from each kernel's own static
    autotuner; interpret mode resolves per platform and kernel family."""
    cfg = I.VegasConfig(neval=neval, ninc=ninc,
                        chunk=min(neval, 1 << 14)).resolve(ig.dim)
    state = I.init_state(ig, cfg, jax.random.PRNGKey(0))
    key = jax.random.fold_in(state.key, 0)

    def jitted(fn, **kw):
        return jax.jit(functools.partial(
            fn, integrand=ig, nstrat=cfg.nstrat, n_cap=cfg.n_cap,
            chunk=cfg.chunk, **kw))

    t_ref = timeit(jitted(F.fill_reference), state.edges, state.n_h, key)
    t_base = timeit(jitted(F.fill_pallas, fused_cubes=False),
                    state.edges, state.n_h, key)
    t_fused = timeit(jitted(F.fill_pallas, fused_cubes=True),
                     state.edges, state.n_h, key)
    t_gpu = timeit(jitted(F.fill_pallas_gpu),
                   state.edges, state.n_h, key)
    return t_ref, t_base, t_fused, t_gpu


def _phases(ig, neval, ninc=1024):
    """Per-phase fill decomposition (module docstring): returns measured
    ``rng``/``eval``/``adapt`` seconds plus per-backend ``accumulate``
    (direct for ref, ``total - rng - eval`` for the in-kernel backends)."""
    import jax.numpy as jnp

    cfg = I.VegasConfig(neval=neval, ninc=ninc,
                        chunk=min(neval, 1 << 14)).resolve(ig.dim)
    state = I.init_state(ig, cfg, jax.random.PRNGKey(0))
    key = jax.random.fold_in(state.key, 0)
    dim, chunk, n_chunks = ig.dim, cfg.chunk, cfg.n_cap // cfg.chunk
    cubes = strat.cubes_for_slice(state.n_h, 0, cfg.n_cap).reshape(
        n_chunks, chunk)

    def scan(body):
        def prog(k):
            def step(c, g):
                return c + body(k, g), None
            out, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                                  jnp.arange(n_chunks))
            return out
        return jax.jit(prog)

    # rng: the chunk-keyed uniform stream every backend consumes (the
    # in-kernel backends regenerate exactly this inside the kernel).
    t_rng = timeit(scan(lambda k, g: jnp.sum(jax.random.uniform(
        jax.random.fold_in(k, g), (chunk, dim)))), key)

    # eval: transform + integrand on that stream (map lookup + jacobian).
    def eval_body(k, g):
        u = jax.random.uniform(jax.random.fold_in(k, g), (chunk, dim))
        cube = cubes[g]
        w, _, _ = F._eval_chunk(state.edges, cube, u, ig, cfg.nstrat,
                                cfg.n_cubes)
        return jnp.sum(w)
    t_eval = timeit(scan(eval_body), key)

    # accumulate/ref: the scatter-add program on precomputed (w, iy, cube).
    def acc_body(k, g):
        u = jax.random.uniform(jax.random.fold_in(k, g), (chunk, dim))
        cube = cubes[g]
        w, iy, valid = F._eval_chunk(state.edges, cube, u, ig, cfg.nstrat,
                                     cfg.n_cubes)
        ms, _ = vmap_.accumulate_map_weights(iy, w * w,
                                             valid.astype(w.dtype), cfg.ninc)
        s1 = jnp.zeros((cfg.n_cubes + 1,), w.dtype).at[cube].add(w)
        return jnp.sum(ms) + jnp.sum(s1)
    t_acc_ref = max(timeit(scan(acc_body), key) - t_eval, 0.0)

    # adapt: map + stratification update (backend-independent).
    fill_j = jax.jit(functools.partial(
        F.fill_reference, integrand=ig, nstrat=cfg.nstrat, n_cap=cfg.n_cap,
        chunk=cfg.chunk))
    res = jax.block_until_ready(fill_j(state.edges, state.n_h, key))
    _, _, d_h = F.estimate_from_cubes(res, state.n_h)
    t_adapt = timeit(jax.jit(lambda e, r, d: (
        vmap_.adapt_edges(e, r.map_sums, r.map_counts, 0.5),
        strat.adapt_nh(d, 0.75, cfg.neval))), state.edges, res, d_h)

    # accumulate/<pallas backend>: derived from each backend's fill total.
    t_ref, t_base, t_fused, t_gpu = _fill_backends(ig, neval, ninc=ninc)
    acc = {"ref": t_acc_ref,
           "pallas-fused": max(t_fused - t_rng - t_eval, 0.0),
           "pallas-gpu": max(t_gpu - t_rng - t_eval, 0.0)}
    return dict(rng=t_rng, eval=t_eval, adapt=t_adapt, accumulate=acc)


def run(fast=True):
    evals = [10**5, 10**6] if fast else [10**5, 10**6, 10**7]
    for name, mk in [("roos_arnold", make_roos_arnold),
                     ("ridge", lambda: make_ridge(n_peaks=1000))]:
        ig = mk()
        for ne in evals:
            s = _sections(ig, ne)
            pct = {k: 100 * v / s["total"] for k, v in s.items() if k != "total"}
            emit(f"table1/{name}/neval={ne:.0e}/fill", s["fill"],
                 f"fill%={pct['fill']:.1f} init%={pct['init']:.1f} "
                 f"update%={pct['update']:.1f} results%={pct['results']:.1f}",
                 n_eval=ne, backend="ref")

    # Fill perf trajectory: P-V2 baseline vs P-V3 fused vs the Triton
    # scatter kernel at the smoke shapes (full mode adds a second decade).
    pallas_evals = [10**5] if fast else [10**5, 10**6]
    # A BENCH_fill.json row is only comparable to rows that ran the kernel
    # the same way: record the resolved interpret mode (platform autodetect,
    # kernels.resolve_interpret, per kernel family) in every pallas-backed
    # fill row, so trajectory tooling never pits an interpreter number
    # against a compiled one.
    interp = kernels.resolve_interpret(None)
    interp_gpu = kernels.resolve_interpret(None, family="gpu")
    for name, ig in [("roos_arnold", make_roos_arnold()),
                     ("cosine_d6", make_cosine(dim=6))]:
        for ne in pallas_evals:
            t_ref, t_base, t_fused, t_gpu = _fill_backends(ig, ne)
            emit(f"table1/{name}/neval={ne:.0e}/fill_pallas", t_base,
                 f"vs_ref={t_ref / t_base:.3f}x", n_eval=ne, backend="pallas",
                 interpret=interp)
            emit(f"table1/{name}/neval={ne:.0e}/fill_fused", t_fused,
                 f"speedup_vs_pallas={t_base / t_fused:.2f}x",
                 n_eval=ne, backend="pallas_fused", interpret=interp)
            emit(f"table1/{name}/neval={ne:.0e}/fill_gpu", t_gpu,
                 f"vs_ref={t_ref / t_gpu:.3f}x "
                 f"vs_fused={t_fused / t_gpu:.3f}x",
                 n_eval=ne, backend="pallas_gpu", interpret=interp_gpu)

    # Per-phase decomposition (one smoke shape: the phases suite re-times
    # every backend's full fill, so keep its footprint to one integrand).
    ig = make_roos_arnold()
    ne = pallas_evals[0]
    ph = _phases(ig, ne)
    for phase in ("rng", "eval", "adapt"):
        emit(f"table1/phases/roos_arnold/neval={ne:.0e}/{phase}", ph[phase],
             "backend-independent (JAX-level)", n_eval=ne)
    for backend, t in ph["accumulate"].items():
        how = ("measured scatter-add program" if backend == "ref"
               else "derived: fill_total - rng - eval")
        emit(f"table1/phases/roos_arnold/neval={ne:.0e}/accumulate/{backend}",
             t, how, n_eval=ne, backend=backend,
             interpret=(None if backend == "ref"
                        else interp_gpu if backend == "pallas-gpu"
                        else interp))


if __name__ == "__main__":
    run()
