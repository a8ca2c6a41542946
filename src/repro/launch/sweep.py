"""Batched scenario-sweep CLI: B integrands, one jitted program.

  PYTHONPATH=src python -m repro.launch.sweep --family asian --batch 8 \
      --neval 100000 --iters 10 [--compare-serial] [--cache maps.npz] \
      [--backend pallas-fused] [--shard]

Sweeps a parameterized integrand family (repro.batch.family.FAMILIES)
through the unified execution engine.  ``--shard`` composes the batch axis
with the mesh axis — B scenarios × D local devices as ONE jitted program
(the sharded batched path, DESIGN.md §9.3); ``--compare-serial`` also times
the B-serial-runs baseline and reports per-scenario agreement; ``--cache``
warm-starts the importance maps from (and refreshes) an on-disk map cache;
``--rtol``/``--atol`` set a per-scenario convergence target — converged
scenarios stop adapting (masked while_loop iterations, §10) and the sweep
reports the scenario-iterations saved.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.batch import MapCache, run_batch, run_serial
from repro.batch.family import FAMILIES
from repro.core import VegasConfig
from repro.engine import make_plan
from repro.launch import env
from repro.launch.integrate import add_execution_args, build_execution


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=sorted(FAMILIES), default="gaussian")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--neval", type=int, default=100_000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--skip", type=int, default=3)
    ap.add_argument("--ninc", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16_384)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache", default=None,
                    help="path to an .npz map cache (warm start + refresh)")
    ap.add_argument("--compare-serial", action="store_true",
                    help="also run the B-serial-calls baseline and compare")
    add_execution_args(ap)
    args = ap.parse_args(argv)
    env.apply_env_args(args)
    env.use_compile_cache()

    family = FAMILIES[args.family](args.batch)
    execution = build_execution(args)
    cfg = VegasConfig(neval=args.neval, max_it=args.iters, skip=args.skip,
                      ninc=args.ninc, chunk=args.chunk, execution=execution)
    if args.plan:
        print(make_plan(family, cfg).describe())
        return None
    key = jax.random.PRNGKey(args.seed)
    cache = MapCache(args.cache) if args.cache else None

    if args.grad != "off":
        # Grad sweep: per-scenario parameter gradients (the Greeks path).
        # The grad program takes no warm-start cache / serial baseline.
        if cache is not None or args.compare_serial:
            ap.error("--grad does not combine with --cache/--compare-serial")
        t0 = time.perf_counter()
        res = run_batch(family, cfg, key=key)
        dt = time.perf_counter() - t0
        print(f"family={family.name} B={res.batch_size} dim={family.dim} "
              f"grad={res.mode} [{execution.describe()}]")
        names = sorted(res.grad) if isinstance(res.grad, dict) else None
        params = np.asarray(jax.tree.leaves(family.params)[0])
        for b in range(res.batch_size):
            line = (f"  [{b}] param={params[b]}  "
                    f"{res.mean[b]:.8g} +- {res.sdev[b]:.3g}")
            if names:
                for n in names:
                    line += f"  d/d{n}={np.asarray(res.grad[n])[b]:+.5g}"
                    if res.grad_sdev is not None:
                        line += f"(+-{np.asarray(res.grad_sdev[n])[b]:.2g})"
            else:
                g = np.asarray(jax.tree.leaves(res.grad)[0][b]).ravel()
                line += "  grad=" + np.array2string(g, precision=4)
            print(line)
        print(f"  grad sweep wall = {dt:.2f}s")
        return res

    t0 = time.perf_counter()
    res = run_batch(family, cfg, key=key, cache=cache)
    dt_batch = time.perf_counter() - t0

    print(f"family={family.name} B={res.batch_size} dim={family.dim} "
          f"neval={args.neval} iters={args.iters} "
          f"warm_start={res.warm_started} [{execution.describe()}]")
    params = np.asarray(jax.tree.leaves(family.params)[0])
    for b in range(res.batch_size):
        p = params[b] if params.ndim == 1 else params[b].tolist()
        line = (f"  [{b}] param={p}  {res.mean[b]:.8g} +- {res.sdev[b]:.3g} "
                f"(chi2/dof {res.chi2_dof[b]:.2f}, "
                f"it {res.n_it_used[b]}/{args.iters})")
        if family.targets is not None:
            pull = (res.mean[b] - family.targets[b]) / max(res.sdev[b], 1e-30)
            line += f"  target={family.targets[b]:.8g} pull={pull:+.2f}"
        print(line)
    print(f"  batched wall = {dt_batch:.2f}s "
          f"({args.neval * args.iters * res.batch_size / dt_batch:,.0f} evals/s)")
    saved = args.iters * res.batch_size - int(res.n_it_used.sum())
    if saved:
        print(f"  early stop saved {saved} of {args.iters * res.batch_size} "
              f"scenario-iterations (per-scenario stop masks)")

    if args.compare_serial:
        t0 = time.perf_counter()
        serial = run_serial(family, cfg, key=key)
        dt_serial = time.perf_counter() - t0
        worst = max(abs(res.mean[b] - serial[b].mean)
                    / max(np.hypot(res.sdev[b], serial[b].sdev), 1e-30)
                    for b in range(res.batch_size))
        print(f"  serial wall  = {dt_serial:.2f}s  "
              f"speedup = {dt_serial / dt_batch:.2f}x  "
              f"worst batched-vs-serial gap = {worst:.3f} combined sigma")
    return res


if __name__ == "__main__":
    main()
