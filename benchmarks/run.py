"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig8,table1,...]
      [--json OUT.json] [--gate-fill]

Prints ``name,us_per_call,derived`` CSV rows.  Default (fast) mode scales
n_eval down so the suite completes on a single CPU core in minutes; --full
uses paper-scale parameters.

``--json OUT.json`` additionally writes every row as a structured record
(name, us_per_call, derived, n_eval, backend where known) plus run metadata
(git sha, jax version/backend, mode) — and extracts three trajectory
artifacts next to it: the fill rows into ``BENCH_fill.json`` (the kernel
trajectory DESIGN.md §7 tracks across PRs), the end-to-end ``run/*`` rows
into ``BENCH_run.json`` (whole-run wall clock per backend,
benchmarks/bench_runs.py), and the ``serve/*`` rows into
``BENCH_serve.json`` (service requests/sec at fixed precision,
benchmarks/bench_serve.py).

``--gate-fill`` turns the P-V2 vs P-V3 comparison into a regression gate:
exit nonzero if any ``fill_fused`` row is slower than its ``fill_pallas``
twin (CI's bench-smoke job runs ``--only table1,batch --json --gate-fill``).
``--gate-run`` does the same for the autotuner (ISSUE 8): the
``run/autotune/*`` rows pair each shape's default-knob timing with its
``autotune=True`` twin, and the gate fails if autotuning made any shape
slower — or never made one faster.  The ``calibrate`` suite (not in the
default set's hot path, but first when selected) measures the cost-model
grid and writes ``COST_TABLE.json`` for those autotuned rows to consume.

``--gate-abs`` is the ABSOLUTE trajectory gate (ISSUE 9): every current
fill/run row is paired with the best committed prior row of the same
(name, backend, device_kind, interpret) — read from ``BENCH_fill.json`` /
``BENCH_run.json`` on disk BEFORE ``--json`` overwrites them — and the gate
fails on a >1.10x wall-clock regression.  Rows with no prior are skipped
(a new shape/backend/device cannot regress against nothing), and so are
rows on the generic ``device_kind="cpu"`` (absolute seconds are not
comparable across unidentified hosts — see ``gate_abs``), so the gate
auto-arms as real-hardware artifacts accumulate and auto-skips on silicon
with no history — the compiled-GPU path's first run records, the second
gates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def fill_rows(rows: list[dict]) -> list[dict]:
    """The fill perf-trajectory subset: every row timing a fill variant."""
    return [r for r in rows if "/fill" in r["name"]]


def run_rows(rows: list[dict]) -> list[dict]:
    """The end-to-end trajectory subset: whole-run timings (bench_runs.py)."""
    return [r for r in rows if r["name"].startswith("run/")]


def serve_rows(rows: list[dict]) -> list[dict]:
    """The serving-throughput subset: requests/sec rows (bench_serve.py)."""
    return [r for r in rows if r["name"].startswith("serve/")]


def _accum(r: dict) -> str:
    """A row's accumulation dtype for gate pairing (§15).  Rows stamped
    before accum_dtype existed carry none — they were all f32-accumulated,
    so they normalize to 'float32' and can only ever pair with f32 rows;
    a widened-f64 run is never compared against an f32 timing."""
    return r.get("accum_dtype") or "float32"


def gate_run(rows: list[dict]) -> list[str]:
    """The autotuner's regression gate (ISSUE 8): pair each
    ``run/autotune/<shape>/autotuned`` row with its ``/default`` twin and
    return a failure message per pair where autotuning made the shape
    slower than the default knobs (beyond a 5% timing-noise allowance) —
    plus one failure if NO measured pair came out strictly faster (an
    autotuner that never wins is not earning its keep)."""
    base = {r["name"].replace("/default", ""): r for r in rows
            if r["name"].startswith("run/autotune/")
            and r["name"].endswith("/default")}
    failures, pairs, wins = [], 0, 0
    for r in rows:
        if not (r["name"].startswith("run/autotune/")
                and r["name"].endswith("/autotuned")):
            continue
        twin = base.get(r["name"].replace("/autotuned", ""))
        if twin is None:
            continue
        if r.get("interpret") != twin.get("interpret"):
            # Same universe rule as gate_fill: interpreter vs compiled
            # timings are incomparable.
            continue
        if _accum(r) != _accum(twin):
            # So are f32- vs f64-accumulated runs (§15).
            continue
        pairs += 1
        if r["us_per_call"] > twin["us_per_call"] * 1.05:
            failures.append(
                f"GATE: {r['name']} ({r['us_per_call']:.0f}us, "
                f"chunk={r.get('chunk')} tile={r.get('tile')}) slower than "
                f"{twin['name']} ({twin['us_per_call']:.0f}us, "
                f"chunk={twin.get('chunk')} tile={twin.get('tile')})")
        if r["us_per_call"] < twin["us_per_call"]:
            wins += 1
    if pairs == 0:
        failures.append("GATE: no autotuned/default pair was measured — "
                        "--gate-run has nothing to check")
    elif wins == 0:
        failures.append(f"GATE: autotuning won on none of the {pairs} "
                        f"measured shapes")
    return failures


#: --gate-abs failure threshold: current / best-prior wall clock.
ABS_GATE_RATIO = 1.10


def load_prior_rows(paths: list[str]) -> list[dict]:
    """Prior BENCH artifact rows for ``--gate-abs`` — tolerant of missing
    or malformed files (no history is a skip, not an error)."""
    rows: list[dict] = []
    for path in paths:
        try:
            with open(path) as f:
                rows.extend(json.load(f).get("rows", []))
        except (OSError, ValueError):
            continue
    return rows


def gate_abs(rows: list[dict], prior_rows: list[dict],
             ratio: float = ABS_GATE_RATIO) -> tuple[list[str], int, int]:
    """The absolute wall-clock gate: pair each current row with the BEST
    prior row of the same (name, backend, device_kind, interpret) and fail
    when current > ``ratio`` x prior.  Prior rows recorded before
    device_kind stamping match any device (legacy wildcard); rows with no
    prior at all are skipped.  Rows whose device_kind is the generic
    ``"cpu"`` are also skipped: that string names no actual hardware, so
    "same device_kind" cannot hold across hosts (CI runners vs dev boxes),
    and measured same-host run-to-run variance on the small CPU rows
    (up to ~1.3x) swamps the threshold — absolute seconds only gate where
    they are comparable, i.e. real accelerator rows whose device_kind is
    a hardware model string (DESIGN.md §14.4).  Returns
    (failures, checked, skipped)."""
    best: dict[tuple, float] = {}
    legacy: dict[tuple, float] = {}
    for r in prior_rows:
        us = r.get("us_per_call")
        if not us:
            continue
        k = (r.get("name"), r.get("backend"), r.get("interpret"), _accum(r))
        if r.get("device_kind") is None:
            legacy[k] = min(legacy.get(k, us), us)
        else:
            kd = k + (r["device_kind"],)
            best[kd] = min(best.get(kd, us), us)
    failures, checked, skipped = [], 0, 0
    for r in rows:
        if (r.get("device_kind") or "cpu") == "cpu":
            skipped += 1
            continue
        k = (r.get("name"), r.get("backend"), r.get("interpret"), _accum(r))
        prior = best.get(k + (r.get("device_kind"),), legacy.get(k))
        if prior is None:
            skipped += 1
            continue
        checked += 1
        if r["us_per_call"] > prior * ratio:
            failures.append(
                f"GATE: {r['name']} ({r['us_per_call']:.0f}us, "
                f"backend={r.get('backend')} "
                f"device_kind={r.get('device_kind')}) regressed "
                f"{r['us_per_call'] / prior:.2f}x vs best prior "
                f"{prior:.0f}us (limit {ratio:.2f}x)")
    return failures, checked, skipped


def gate_fill(rows: list[dict]) -> list[str]:
    """Pair each fused fill row with its baseline-pallas twin; return a
    failure message per pair where fused is slower."""
    base = {r["name"].replace("/fill_pallas", ""): r for r in rows
            if r["name"].endswith("/fill_pallas")}
    failures = []
    for r in rows:
        if not r["name"].endswith("/fill_fused"):
            continue
        twin = base.get(r["name"].replace("/fill_fused", ""))
        if twin is None:
            continue
        if r.get("interpret") != twin.get("interpret"):
            # Interpreter vs compiled-Mosaic timings are different universes;
            # comparing across modes gates nothing real.
            continue
        if _accum(r) != _accum(twin):
            # Precision policies are different universes too (§15).
            continue
        if r["us_per_call"] > twin["us_per_call"]:
            failures.append(
                f"GATE: {r['name']} ({r['us_per_call']:.0f}us) slower than "
                f"{twin['name']} ({twin['us_per_call']:.0f}us)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="write structured results + BENCH_fill.json")
    ap.add_argument("--gate-fill", action="store_true",
                    help="exit nonzero if the fused fill is slower than the "
                         "baseline pallas fill on any measured shape")
    ap.add_argument("--gate-run", action="store_true",
                    help="exit nonzero if an autotuned run is slower than "
                         "its default-knob twin on any measured shape, or "
                         "if autotuning never won")
    ap.add_argument("--gate-abs", action="store_true",
                    help="exit nonzero if any fill/run row regressed more "
                         "than 1.10x vs the best prior BENCH row of the "
                         "same (name, backend, device_kind, interpret); "
                         "rows with no prior are skipped")
    args = ap.parse_args()
    fast = not args.full
    only = set(filter(None, args.only.split(",")))

    # --gate-abs priors must be read BEFORE --json overwrites the artifacts:
    # the committed repo copies (cwd) plus any previous copies in the --json
    # output directory.
    prior_rows: list[dict] = []
    if args.gate_abs:
        dirs = ["."]
        if args.json:
            dirs.append(os.path.dirname(os.path.abspath(args.json)))
        prior_rows = load_prior_rows(
            [os.path.join(d, f) for d in dict.fromkeys(dirs)
             for f in ("BENCH_fill.json", "BENCH_run.json")])

    from . import (bench_applications, bench_batch, bench_breakdown,
                   bench_calibrate, bench_grad, bench_integrands,
                   bench_multidevice, bench_runs, bench_scaling, bench_serve,
                   bench_stratification)
    from . import common

    suites = {
        "calibrate": bench_calibrate,
        "table1": bench_breakdown,
        "table7": bench_integrands,
        "fig3": bench_scaling,
        "fig8": bench_stratification,
        "table8": bench_multidevice,
        "table9_10": bench_applications,
        "batch": bench_batch,
        "run": bench_runs,
        "grad": bench_grad,
        "serve": bench_serve,
    }
    common.reset_rows()
    print("name,us_per_call,derived")
    failed = []
    for key, mod in suites.items():
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod.run(fast=fast)
        except Exception as e:  # run the other suites, then exit non-zero
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}", file=sys.stdout)
            failed.append(key)
        print(f"{key}/_suite_wall,{(time.time()-t0)*1e6:.0f},",
              file=sys.stdout)

    if args.json:
        import jax
        meta = {
            "git_sha": common.git_sha(),
            "jax_version": jax.__version__,
            "jax_backend": jax.default_backend(),
            "mode": "full" if args.full else "fast",
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(meta, f, indent=1)
        out_dir = os.path.dirname(os.path.abspath(args.json))
        wrote = [args.json]
        for fname, subset in [("BENCH_fill.json", fill_rows(common.ROWS)),
                              ("BENCH_run.json", run_rows(common.ROWS)),
                              ("BENCH_serve.json", serve_rows(common.ROWS))]:
            if not subset:
                continue
            path = os.path.join(out_dir, fname)
            with open(path, "w") as f:
                json.dump({**{k: v for k, v in meta.items() if k != "rows"},
                           "rows": subset}, f, indent=1)
            wrote.append(path)
        print(f"# wrote {' and '.join(wrote)}", file=sys.stderr)

    if args.gate_fill:
        failures = gate_fill(common.ROWS)
        for msg in failures:
            print(msg, file=sys.stderr)
        if failures:
            sys.exit(2)
        n = sum(1 for r in common.ROWS
                if r["name"].endswith("/fill_fused")
                and r["name"].replace("/fill_fused", "/fill_pallas")
                in {x["name"] for x in common.ROWS})
        if n == 0:
            # A gate that measured nothing is a broken gate, not a green one
            # (e.g. --only dropped table1, or the fill rows were renamed).
            print("GATE: no fused/baseline fill pair was measured — "
                  "--gate-fill has nothing to check", file=sys.stderr)
            sys.exit(2)
        print(f"# fill gate OK ({n} fused shapes measured)", file=sys.stderr)

    if args.gate_run:
        failures = gate_run(common.ROWS)
        for msg in failures:
            print(msg, file=sys.stderr)
        if failures:
            sys.exit(2)
        n = sum(1 for r in common.ROWS
                if r["name"].startswith("run/autotune/")
                and r["name"].endswith("/autotuned"))
        print(f"# run gate OK ({n} autotuned shapes measured)",
              file=sys.stderr)

    if args.gate_abs:
        failures, checked, skipped = gate_abs(
            fill_rows(common.ROWS) + run_rows(common.ROWS), prior_rows)
        for msg in failures:
            print(msg, file=sys.stderr)
        if failures:
            sys.exit(2)
        print(f"# abs gate OK ({checked} rows checked vs prior, "
              f"{skipped} skipped: generic-cpu or no prior)",
              file=sys.stderr)

    if failed:
        print(f"# suites failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
