"""From a profiler trace of the window to device-time numbers.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes
and keeps two things: each device's operations (the ``XLA Ops`` line of a
``/device:TPU:n`` plane: HLO text, start, duration) and, on the
host, the threads that carry the harness's ``bench.*`` spans.  ``reduce``
then splits each device's time in the window into four classes:

* ``kernel``: the Mosaic fill kernel (a TPU custom call);
* ``collective``: all-reduce, all-gather, reduce-scatter, permutes;
* ``xla``: every other operation;
* ``idle``: the window less the union of the intervals in which any
  operation ran.

Each idle gap is put down to what the host was doing at its midpoint: the
innermost ``bench.*`` span there, and within it the innermost event of the
program or of JAX on the same thread.  Times are seconds; a device's
numbers are averaged over the devices used.
"""

from __future__ import annotations

import gzip
import json
from collections import defaultdict
from pathlib import Path

#: A TPU operation's event carries its HLO text.  A Pallas kernel runs as
#: a custom call to Mosaic; a collective is one of these opcodes.
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
DEVICE = "/device:TPU:"
#: Characters of an operation's HLO text kept as its name in a breakdown.
LABEL = 96
WINDOW_SPAN = "bench.window"


def classify(text: str) -> str:
    """``kernel``, ``collective`` or ``xla`` from an operation's HLO text
    (``%name = shape opcode(operands), attributes``)."""
    if KERNEL_MARK in text:
        return "kernel"
    rhs = text.split(" = ", 1)[-1]
    if any(f" {op}(" in rhs or f" {op}-start(" in rhs
           for op in COLLECTIVE_OPS):
        return "collective"
    return "xla"


# --- reading -------------------------------------------------------------------

def load_events(logdir) -> dict:
    """``{"devices": {plane: [[hlo_text, start_ns, dur_ns], ...]},
    "host": {thread: [[name, start_ns, dur_ns], ...]}}`` from the newest
    ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(str(paths[-1]))
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE):
            devices[plane.name] = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if not e.name.startswith("$")]
                if any(n.startswith("bench.") for n, _, _ in evs):
                    host[f"{plane.name}/{line.name}"] = evs
    return {"devices": devices, "host": host}


def save_events(events: dict, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --- reducing ------------------------------------------------------------------

def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def leaves(ops):
    """The operations that enclose no other: a ``while`` or a called
    computation is listed around the operations it runs, which would
    otherwise count twice."""
    ev = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ev)
    stack = []
    for i, (_, s, _) in enumerate(ev):
        while stack and ev[stack[-1]][1] + ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ev, parent) if not p]


def _window(events) -> tuple[float, float]:
    spans = [(s, s + d) for evs in events["host"].values()
             for n, s, d in evs if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [(s, s + d) for evs in events["devices"].values()
           for _, s, d in evs]
    return min(s for s, _ in ops), max(e for _, e in ops)


def _doing(host_events, times):
    """What the host was doing at each of ``times`` (ascending):
    ``span/event``, the innermost ``bench.*`` span and the innermost other
    event covering that time."""
    evs = sorted(host_events, key=lambda e: e[1])
    active, j, out = [], 0, []
    for t in times:
        while j < len(evs) and evs[j][1] <= t:
            active.append(evs[j])
            j += 1
        active = [e for e in active if e[1] + e[2] > t]
        spans = [e for e in active
                 if e[0].startswith("bench.") and e[0] != WINDOW_SPAN]
        inner = [e for e in active if not e[0].startswith("bench.")]
        label = min(spans, key=lambda e: e[2])[0] if spans else WINDOW_SPAN
        if inner:
            label += "/" + min(inner, key=lambda e: e[2])[0]
        out.append(label)
    return out


def reduce(events: dict, n_devices: int = 1, top: int = 10) -> dict:
    """Device time of the window by class, idle gaps by host activity.

    Returns seconds: ``kernel_s``, ``collective_s``, ``xla_s``, ``busy_s``
    (each averaged over the first ``n_devices`` TPU planes), ``window_s``,
    and ``breakdown`` with the ``top`` device operations and idle-gap causes
    by summed seconds."""
    w0, w1 = _window(events)
    planes = sorted(p for p in events["devices"] if p.startswith(DEVICE))
    planes = planes[:n_devices]
    if not planes:
        raise ValueError("the trace holds no TPU plane")
    host = [e for evs in events["host"].values() for e in evs]
    by_class = defaultdict(float)
    by_op = defaultdict(float)
    gaps = defaultdict(float)
    busy = 0.0
    for p in planes:
        live = []
        for name, s, d in leaves(events["devices"][p]):
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            live.append((s, e))
            by_class[classify(name)] += (e - s) / 1e9
            by_op[name[:LABEL]] += (e - s) / 1e9
        merged = union(live)
        busy += sum(e - s for s, e in merged) / 1e9
        bounds = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(bounds[::2], bounds[1::2]) if e > s]
        for (s, e), why in zip(idle, _doing(host, [(s + e) / 2
                                                   for s, e in idle])):
            gaps[why] += (e - s) / 1e9
    n = len(planes)

    def ranked(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"kernel_s": by_class["kernel"] / n,
            "collective_s": by_class["collective"] / n,
            "xla_s": by_class["xla"] / n,
            "busy_s": busy / n,
            "window_s": (w1 - w0) / 1e9,
            "breakdown": {"device_ops": ranked(by_op),
                          "idle_gaps": ranked(gaps)}}
