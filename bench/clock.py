"""Seconds and counts of JAX's own tracing, lowering and compile events.

``CompileClock`` is ``chip_smoke.py``'s clock, with counts beside the
seconds: a backend compile that the persistent cache served is counted as a
cache hit, one it did not as a miss, so a window can show that it compiled
nothing new.
"""

from __future__ import annotations

_SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
            "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
            "/jax/core/compile/backend_compile_duration": "compile_s"}
_COUNTS = {"/jax/core/compile/backend_compile_duration": "compiles",
           "/jax/compilation_cache/cache_hits": "cache_hits"}


class CompileClock:
    def __init__(self, jax):
        self.totals = {k: 0.0 for k in _SECONDS.values()}
        self.totals.update({k: 0 for k in _COUNTS.values()})
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in _SECONDS:
            self.totals[_SECONDS[event]] += duration
        if event in _COUNTS:
            self.totals[_COUNTS[event]] += 1

    def _on_event(self, event, **_):
        if event in _COUNTS:
            self.totals[_COUNTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.totals)

    def since(self, before: dict) -> dict:
        """Seconds and counts since ``before``, with the compiles that the
        persistent cache did not serve as ``cache_misses``."""
        d = {k: self.totals[k] - before[k] for k in self.totals}
        d["cache_misses"] = d["compiles"] - d["cache_hits"]
        return d
