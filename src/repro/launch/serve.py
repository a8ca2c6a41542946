"""Long-lived sweep-service CLI (DESIGN.md §12).

  PYTHONPATH=src python -m repro.launch.serve --demo 8 --rtol 0.05
  PYTHONPATH=src python -m repro.launch.serve --requests reqs.jsonl \
      --cache maps.npz --stats-json stats.json

Runs a `repro.serve.SweepService` with its background micro-batching
worker and drives it with either a generated demo burst (``--demo N``
gaussian requests) or a JSONL file (``--requests``, one
`IntegrationRequest` object per line, e.g.
``{"family": "gaussian", "params": [0.3], "rtol": 0.01, "seed": 7}``).
Rejected requests print their one-line PlanError; served requests print
their estimates and billing record; the run ends with the ``stats()``
snapshot (``--stats-json`` writes it for dashboards).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from repro.engine import PlanError
from repro.launch import env
from repro.serve import IntegrationRequest, SweepService


def _load_requests(path: str) -> list[IntegrationRequest]:
    out = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                obj = json.loads(line)
                fkw = obj.get("family_kwargs")
                if isinstance(fkw, dict):
                    obj["family_kwargs"] = tuple(sorted(fkw.items()))
                out.append(IntegrationRequest(**obj))
            except (json.JSONDecodeError, TypeError) as e:
                raise SystemExit(f"{path}:{lineno}: bad request: {e}")
    return out


def _demo_burst(args) -> list[IntegrationRequest]:
    params = np.linspace(0.2, 0.8, args.demo)
    return [IntegrationRequest(
        family=args.family, params=[float(p)], rtol=args.rtol,
        atol=args.atol, time_budget_s=args.time_budget, seed=i,
        neval=args.neval, max_it=args.iters,
        accum_dtype=args.accum_dtype) for i, p in enumerate(params)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--demo", type=int, default=0, metavar="N",
                     help="submit a burst of N single-scenario demo "
                          "requests")
    src.add_argument("--requests", default=None, metavar="FILE.jsonl",
                     help="serve one JSON request per line")
    ap.add_argument("--family", default="gaussian")
    ap.add_argument("--rtol", type=float, default=0.0)
    ap.add_argument("--atol", type=float, default=0.0)
    ap.add_argument("--time-budget", type=float, default=None,
                    help="per-request wall-clock budget (seconds)")
    ap.add_argument("--neval", type=int, default=20_000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--accum-dtype", choices=["float32", "float64"],
                    default=None,
                    help="demo requests' §15 accumulation dtype (float64 "
                         "needs --x64; JSONL requests carry their own "
                         "accum_dtype field)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="scenarios per coalesced micro-batch")
    ap.add_argument("--max-wait", type=float, default=0.02,
                    help="micro-batching window (seconds)")
    ap.add_argument("--cache", default=None,
                    help="shared map-pool path (.npz; warm starts persist "
                         "across service restarts and CLI sweeps)")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-request result timeout (seconds)")
    ap.add_argument("--stats-json", default=None, metavar="OUT.json",
                    help="write the final stats() snapshot")
    ap.add_argument("--cost-table", default=None, metavar="PATH",
                    help="calibrated cost table (engine.autotune) used as "
                         "the budget-calibration prior for classes the "
                         "service has not yet observed")
    env.add_env_args(ap)
    args = ap.parse_args(argv)
    env.apply_env_args(args)
    env.use_compile_cache()

    if args.requests:
        requests = _load_requests(args.requests)
    else:
        if args.demo <= 0:
            args.demo = 4
        requests = _demo_burst(args)

    with SweepService(max_batch=args.max_batch, max_wait_s=args.max_wait,
                      cache=args.cache, cost_table=args.cost_table) as svc:
        tickets = []
        for req in requests:
            try:
                tickets.append(svc.submit(req))
            except PlanError as e:
                print(f"REJECTED {req.family}: {e}")
        for t in tickets:
            r = t.result(timeout=args.timeout)
            print(r)
            for j in range(r.n_scenarios):
                line = (f"  [{j}] {r.mean[j]:.8g} +- {r.sdev[j]:.3g} "
                        f"(it {r.n_it_used[j]}/{r.it_cap[j]})")
                if r.targets is not None:
                    pull = ((r.mean[j] - r.targets[j])
                            / max(float(r.sdev[j]), 1e-30))
                    line += f"  target={r.targets[j]:.8g} pull={pull:+.2f}"
                print(line)

    stats = svc.stats()
    print(f"served {stats['requests']['completed']} requests / "
          f"{stats['requests']['scenarios_completed']} scenarios in "
          f"{stats['batches']['count']} batches "
          f"(mean occupancy {stats['batches']['mean_occupancy']:.1f}, "
          f"cache hit rate {stats['cache']['hit_rate']:.0%}, "
          f"{stats['throughput']['requests_per_s']:.1f} req/s)")
    print(f"billed {stats['iterations']['billed']} scenario-iterations, "
          f"saved {stats['iterations']['saved_vs_max_it']} vs max_it, "
          f"{stats['iterations']['capped_scenarios']} budget-capped")
    if args.stats_json:
        with open(args.stats_json, "w") as f:
            json.dump(stats, f, indent=1)
        print(f"# wrote {args.stats_json}", file=sys.stderr)
    return stats


if __name__ == "__main__":
    main()
