#!/usr/bin/env python3
"""What the program's own spans, scopes and counter say about one cell.

    python3 bench/program_trace.py --workload gaussian_d4.single --seed 7 --seconds 51

The program marks its phases itself (``repro.obs``): host spans
``repro.run``, ``repro.plan``, ``repro.init``, ``repro.program``,
``repro.wait`` and ``repro.finish``; device scopes ``vegas.cube_ids``,
``vegas.estimate``, ``vegas.adapt_nh``, ``vegas.adapt_edges`` and
``vegas.stop`` in the ``op_name`` of the operations traced inside them;
and the counter ``fill.lanes``.  This script runs a cell's window under
the profiler as ``run.py --trace 1`` does, with the same driver and
check, and splits the window by them:

* ``by_scope``: device seconds of the leaf operations by the outermost
  ``vegas.*`` scope in their path (``""`` for none), averaged over
  devices as ``trace.reduce`` averages its classes;
* ``idle_by_span``: idle seconds by the innermost ``repro.*`` span at the
  gap's midpoint (``""`` for none), by ``trace.reduce``'s midpoint rule.

A TPU operation's event carries no ``op_name``.  Its scope path is read
from the HLO of its module, which the trace's ``/host:metadata`` plane
holds under the stat ``Hlo Proto``; the module is the one running on the
device at the operation's start (the ``XLA Modules`` line).

It prints ``scope``, ``idle_span`` and ``window`` lines (the window's
counts, with the difference of each program counter across it), then a
last line of JSON: ``correct``, the readings below, and the numbers of
``trace.reduce`` they are held against.  The readings are per-layer
metrics that ``run.py`` cannot report yet: it keeps only the three fields
of a device operation and reads no counter of the program.

* ``strat.cube_ids.ms_per_iter``: ``vegas.cube_ids`` device time per
  iteration;
* ``adapt.ms_per_iter``: ``vegas.estimate``, ``vegas.adapt_nh``,
  ``vegas.adapt_edges`` and ``vegas.stop`` per iteration;
* ``host.program_idle_ms.integral``: idle time under ``repro.program``
  per integral;
* ``host.run_idle_ms.integral``: idle time under every other ``repro.*``
  span per integral, the host time a program cache does not remove;
* ``fill.lanes_per_s.integral``: the window's ``fill.lanes`` over the
  fill kernel's device time.

A reading whose span, scope or counter the program lacks is left out.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.append(str(BENCH))

import run as harness  # noqa: E402  (``bench/`` is on the path from here)

T = harness.load_module(BENCH / "trace.py")

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: A scope of the program's loop and adaptation (``repro.obs.scope``).
SCOPE = re.compile(r"vegas\.\w+")
#: The prefix of the program's host spans (``repro.obs.span``).
RUN_SPAN = "repro."
ADAPT_SCOPES = ("vegas.estimate", "vegas.adapt_nh", "vegas.adapt_edges",
                "vegas.stop")


# --- reading -------------------------------------------------------------------

def load_events(logdir) -> dict:
    """``trace.load_events``'s ``{"devices": ..., "host": ...}`` from the
    newest ``.xplane.pb`` under ``logdir``, each device operation with a
    fourth field, its scope path (``""`` where its HLO names none)."""
    from jax.profiler import ProfileData
    paths = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    raw = paths[-1].read_bytes()
    op_names = read_op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith(T.DEVICE):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            starts = [m[0] for m in modules]
            ops = []
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                names = (op_names.get(modules[k][2], {})
                         if k >= 0 and e.start_ns < modules[k][1] else {})
                instr = e.name.split(" = ", 1)[0].lstrip("%")
                ops.append([e.name, float(e.start_ns), float(e.duration_ns),
                            names.get(instr, "")])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if not e.name.startswith("$")]
                if any(n.startswith("bench.") for n, _, _ in evs):
                    host[f"{plane.name}/{line.name}"] = evs
    return {"devices": devices, "host": host}


def plain(events: dict) -> dict:
    """The events as ``trace.reduce`` takes them: three fields an operation."""
    return {"devices": {p: [op[:3] for op in ops]
                        for p, ops in events["devices"].items()},
            "host": events["host"]}


def _varint(buf, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of a serialized protobuf message:
    an int for a varint, a memoryview for anything else."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                n, i = _varint(buf, i)
            elif kind in (1, 5):
                n = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind} at {i}")
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def _field(buf, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def read_op_names(xspace: bytes) -> dict:
    """``{module: {instruction: op_name}}`` from the HLO of every module in
    a serialized XSpace's metadata plane.  Field numbers, from
    ``xplane.proto`` and ``hlo.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .bytes_value 6;
    XStatMetadata.id 1, .name 2; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = _fields(plane)
        # Fields come in number order: the name before the large ones.
        if bytes(next((v for n, v in fields if n == 2), b"")).decode() \
                != METADATA_PLANE:
            continue
        fields = list(_fields(plane))
        stat_ids = [_field(_field(e, 2), 1, 0) for n, e in fields if n == 5
                    if bytes(_field(_field(e, 2), 2)).decode() == HLO_STAT]
        for n, entry in fields:
            if n != 4:
                continue
            meta = _field(entry, 2)
            for st in (v for k, v in _fields(meta) if k == 5):
                if _field(st, 1, 0) in stat_ids:
                    out[bytes(_field(meta, 2)).decode()] = _instructions(
                        _field(_field(st, 6), 1))
    return out


def _instructions(module) -> dict:
    """``{instruction: op_name}`` of a serialized ``HloModuleProto``."""
    names = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g == 2:
                op_name = bytes(_field(_field(instr, 7), 2)).decode()
                if op_name:
                    names[bytes(_field(instr, 1)).decode()] = op_name
    return names


# --- reducing ------------------------------------------------------------------

def scope_of(path: str) -> str:
    """The outermost ``vegas.*`` scope in an operation's scope path."""
    m = SCOPE.search(path)
    return m.group(0) if m else ""


def _run_spans(host_events, times):
    """The innermost ``repro.*`` span covering each of ``times``
    (ascending), ``""`` where none does."""
    evs = sorted((e for e in host_events if e[0].startswith(RUN_SPAN)),
                 key=lambda e: e[1])
    active, j = [], 0
    for t in times:
        while j < len(evs) and evs[j][1] <= t:
            active.append(evs[j])
            j += 1
        active = [e for e in active if e[1] + e[2] > t]
        yield min(active, key=lambda e: e[2])[0] if active else ""


def leaves(ops):
    """``trace.leaves`` of operations of three or four fields."""
    return [ops[i] for i, _, _ in T.leaves([(i, op[1], op[2])
                                           for i, op in enumerate(ops)])]


def reduce(events: dict, n_devices: int = 1) -> dict:
    """``by_scope`` and ``idle_by_span`` of the window, in seconds, over the
    first ``n_devices`` TPU planes; the window, the leaves and the gaps are
    ``trace.reduce``'s.  An operation of three fields has no scope."""
    w0, w1 = T._window(events)
    planes = sorted(p for p in events["devices"] if p.startswith(T.DEVICE))
    planes = planes[:n_devices]
    if not planes:
        raise ValueError("the trace holds no TPU plane")
    host = [e for evs in events["host"].values() for e in evs]
    by_scope = defaultdict(float)
    idle_by_span = defaultdict(float)
    for p in planes:
        live = []
        for op in leaves(events["devices"][p]):
            s, e = max(op[1], w0), min(op[1] + op[2], w1)
            if e <= s:
                continue
            live.append((s, e))
            by_scope[scope_of(op[3] if len(op) > 3 else "")] += (e - s) / 1e9
        merged = T.union(live)
        bounds = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(bounds[::2], bounds[1::2]) if e > s]
        for (s, e), span in zip(idle, _run_spans(host, [(s + e) / 2
                                                        for s, e in idle])):
            idle_by_span[span] += (e - s) / 1e9
    n = len(planes)
    return {"by_scope": {k: v / n for k, v in by_scope.items()},
            "idle_by_span": {k: v / n for k, v in idle_by_span.items()}}


def readings(trace: dict, program: dict, window: dict) -> dict:
    """The five per-layer readings, from ``trace.reduce``'s numbers
    (``trace``), this module's (``program``) and the window's counts; each
    is left out where what it reads is absent."""
    iters = window.get("iterations", 0)
    integrals = window.get("integrals", 0)
    scopes, idle = program["by_scope"], program["idle_by_span"]
    out = {}
    if iters > 0 and "vegas.cube_ids" in scopes:
        out["strat.cube_ids.ms_per_iter"] = 1e3 * scopes["vegas.cube_ids"] / iters
    adapt = [scopes[s] for s in ADAPT_SCOPES if s in scopes]
    if iters > 0 and adapt:
        out["adapt.ms_per_iter"] = 1e3 * sum(adapt) / iters
    if integrals > 0 and "repro.program" in idle:
        out["host.program_idle_ms.integral"] = (
            1e3 * idle["repro.program"] / integrals)
    rest = [v for k, v in idle.items()
            if k.startswith(RUN_SPAN) and k != "repro.program"]
    if integrals > 0 and rest:
        out["host.run_idle_ms.integral"] = 1e3 * sum(rest) / integrals
    if window.get("fill.lanes") and trace["kernel_s"] > 0:
        out["fill.lanes_per_s.integral"] = window["fill.lanes"] / trace["kernel_s"]
    return out


# --- running -------------------------------------------------------------------

def measure(args, *, check_device=harness.check_chips) -> dict:
    """One traced window of a cell, checked as ``run.py`` checks it."""
    bench, cell, config, traffic, limits = harness.load_cell(args.workload)
    import jax
    from repro import obs
    harness.use_compile_cache(jax)
    check_device(jax, cell["chips"])
    driver = harness.load_module(
        BENCH / "drivers" / f"{traffic['driver']}.py").Driver(
        config=config, traffic=traffic, limits=limits, seed=args.seed)
    driver.warm_up()
    before = obs.counts()
    with tempfile.TemporaryDirectory(prefix="bench-program-trace-") as tdir:
        with jax.profiler.trace(tdir,
                                profiler_options=harness._trace_options(jax)):
            window = driver.window(args.seconds)
        events = load_events(tdir)
    window.update({k: v - before.get(k, 0)
                   for k, v in obs.counts().items()})
    driver.release()
    checks = driver.check()
    trace = T.reduce(plain(events), n_devices=cell["chips"])
    program = reduce(events, n_devices=cell["chips"])
    return {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "window": {k: v for k, v in window.items()
                       if not isinstance(v, (list, dict))},
            "readings": readings(trace, program, window),
            "trace": {k: v for k, v in trace.items() if k != "breakdown"},
            **program}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out = measure(args)
    for kind, key in (("scope", "by_scope"), ("idle_span", "idle_by_span"),
                      ("window", "window")):
        for k, v in sorted(out[key].items()):
            print(f"{kind} {k or '-'}={v!r}", flush=True)
    print(json.dumps({k: out[k] for k in ("correct", "readings", "trace")}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
