#!/usr/bin/env python3
"""Readings that set the limits of a cell's check.

    python3 bench/control.py --workload gaussian_d4.single --seeds 11 12 13 --seconds 10 --side both

For each seed, ``program`` runs the cell's window as ``bench/run.py`` does
and prints every number its check can compare: the largest over a dozen
seeds or more is a limit's lower reading.  ``control`` puts the reference,
run in the precision below the configuration's (``bfloat16`` for
``float32``), in the program's place for the same inputs and prints the
same numbers: the smallest over three seeds or more is a limit's upper
reading.  One JSON line per seed and side, all from one process.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness

#: The precision below each one a configuration may state.
LOWER = {"float32": "bfloat16"}


def readings(workload: str, seeds, seconds: float, side: str,
             config_override=None, traffic_override=None,
             check_device=harness.check_chips):
    """Yield one ``{"seed", "side", <number>: value}`` dict per seed and
    side."""
    bench, cell, config, traffic, limits = harness.load_cell(workload)
    config = dict(config, **(config_override or {}))
    traffic = dict(traffic, **(traffic_override or {}))
    import jax
    import jax.numpy as jnp
    harness.use_compile_cache(jax)
    check_device(jax, cell["chips"])
    driver_cls = harness.load_module(
        harness.BENCH / "drivers" / f"{traffic['driver']}.py").Driver
    lower = getattr(jnp, LOWER[config["dtype"]])
    for seed in seeds:
        driver = driver_cls(config=config, traffic=traffic, limits=limits,
                            seed=seed)
        driver.warm_up()
        w = driver.window(seconds)
        driver.release()
        if side in ("program", "both"):
            yield {"seed": seed, "side": "program",
                   "attempted": w["attempted"], "failed": w["failed"],
                   **driver.numbers()}
        if side in ("control", "both"):
            yield {"seed": seed, "side": "control",
                   **driver.numbers(candidate=driver.control(lower))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--side", choices=("program", "control", "both"),
                    default="both")
    args = ap.parse_args(argv)
    for r in readings(args.workload, args.seeds, args.seconds, args.side):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
