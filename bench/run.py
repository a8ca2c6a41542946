#!/usr/bin/env python3
"""The on-chip benchmark of the VEGAS+ system: one cell, one run.

    python3 bench/run.py --workload gaussian_d4.single --seed 7 --seconds 10 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration, whose
file is ``bench/configs/<config>.json``, and a traffic mix, whose file is
``bench/traffic/<traffic>.json``.  The mix names its driver,
``bench/drivers/<driver>.py``, which makes the cell's inputs from the seed,
warms up, runs the measured window and checks what the window produced
against ``bench/reference.py``.  A per-layer metric ``<m>`` is read by
``bench/metrics/<m>.py`` from the trace reduction (``bench/trace.py``) and
the driver's host counters.  Nothing here names a cell: adding a
configuration, a mix or a metric adds files and entries only.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Counts (work in the window, compiles inside it) go on earlier
lines; the last line of standard output is the JSON result, and the
numbers compared for ``correct`` end standard error.

Exits non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse
import importlib.util
import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory of the checkout,
#: so that every run of a cell after the first finds its programs there.
CACHE_DIR = ROOT / ".jax_cache"

sys.path.append(str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


class NoChip(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_json: Path = ROOT / "BENCHMARK.json"):
    """``(benchmark, cell, config, traffic, limits)`` for one workload: the
    limits of its check are ``bench/limits/<workload>.json``."""
    bench = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return bench, cell, config, traffic, limits


def metrics_of(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The cell's end-to-end (trace 0) or per-layer (trace 1) metrics."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def use_compile_cache(jax) -> None:
    """Every program goes to the cache, however short its compile, so that
    nothing the window runs is compiled again in a later run.  Eviction
    stays off whatever the environment says: with it on, two threads that
    compile at once (the service's worker and the main thread) can leave an
    entry without its access-time file, after which every later write to
    the cache fails and every run compiles again."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_line(jax, n_used: int) -> dict:
    devs = jax.devices()[:n_used]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def _trace_options(jax):
    """Device and host events, without the Python function tracer, whose
    cost on every call would land on the host path being measured."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def check_chips(jax, chips: int) -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"no TPU: JAX runs on {backend!r}")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(jax.devices())}")


def run_cell(args, *, check_device=check_chips) -> dict:
    bench, cell, config, traffic, limits = load_cell(args.workload)
    import jax
    use_compile_cache(jax)
    check_device(jax, cell["chips"])

    clock = load_module(BENCH / "clock.py")
    trace_mod = load_module(BENCH / "trace.py")
    compiles = clock.CompileClock(jax)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py").Driver(
        config=config, traffic=traffic, limits=limits, seed=args.seed)
    driver.warm_up()
    setup_s = time.perf_counter() - T_START

    before = compiles.snapshot()
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            with jax.profiler.trace(tdir, profiler_options=_trace_options(jax)):
                window = driver.window(args.seconds)
            events = trace_mod.load_events(tdir)
        if args.keep_trace:
            trace_mod.save_events(events, Path(args.keep_trace)
                                  / f"{cell['name']}.events.json.gz")
        reduced = trace_mod.reduce(events, n_devices=cell["chips"])
    else:
        window = driver.window(args.seconds)
        reduced = None
    window.update(compiles.since(before))
    for k, v in window.items():
        if not isinstance(v, (list, dict)):
            print(f"window {k}={v}", flush=True)

    device = device_line(jax, cell["chips"])
    driver.release()
    checks = driver.check()

    if args.trace:
        ctx = {"trace": reduced, "window": window}
        metrics = {}
        for m in metrics_of(bench, cell, True):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        e2e = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bench, cell, False)}

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if args.trace:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also write the trace's events to DIR")
    args = ap.parse_args(argv)
    result = run_cell(args)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
