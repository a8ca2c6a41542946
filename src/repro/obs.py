"""The program's own measurement: host spans, device scopes and counters.

* :func:`span` marks a host-side phase of a run (``repro.run``,
  ``repro.program``, ...).  It writes into the profiler's own trace, on the
  clock of the device planes, so a gap in device activity can be put down
  to the phase the host was in.  With no profiler running it costs about
  an atomic check.  Use it around host code only: inside a traced function
  it would time the tracing, not the run.
* :func:`scope` labels the operations traced inside it (``vegas.cube_ids``,
  ...).  The label lands in each operation's ``op_name`` metadata, which
  the device trace carries; it adds no device work.
* :func:`count` adds to a process-wide integer counter and :func:`counts`
  snapshots all of them.  Counters only grow: a reader takes the difference
  of two snapshots around the work it measures.  The program counts
  ``fill.lanes`` (lanes the fill ran), ``mesh.psum_bytes`` (bytes each
  device all-reduced), and ``program.built`` / ``program.reused`` (the
  executor's whole-run program cache missed / hit).

Nothing is exported or switched on here: a reader (the benchmark under
``bench/``) starts the profiler and snapshots the counters itself.
"""

from __future__ import annotations

import threading

import jax

_lock = threading.Lock()
_counts: dict[str, int] = {}


def span(name: str):
    """A host span named ``name``: ``with span("repro.plan"): ...``."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope named ``name`` for traced code:
    ``with scope("vegas.estimate"): ...``."""
    return jax.named_scope(name)


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def counts() -> dict[str, int]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)
