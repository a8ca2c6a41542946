"""Service metrics: what `SweepService.stats()` reports (DESIGN.md §12).

One thread-safe accumulator object per service.  Counters are updated by
the admission path (submitted/rejected) and the micro-batcher
(batches/occupancy/cache/latency/billing); :meth:`ServeMetrics.snapshot`
renders the aggregate view the ``stats()`` endpoint and the
``repro.launch.serve --stats-json`` output report.
"""

from __future__ import annotations

import threading


def _mean(xs) -> float:
    return float(sum(xs) / len(xs)) if xs else 0.0


class ServeMetrics:
    """Thread-safe counters + latency/occupancy series for one service."""

    def __init__(self):
        self._lock = threading.Lock()
        self.submitted = 0
        self.rejected = 0
        self.completed = 0
        self.failed = 0
        self.scenarios_completed = 0
        self.batches = 0
        self.occupancy: list[int] = []        # scenarios per batch
        self.coalesced: list[int] = []        # requests per batch
        self.cache_hits = 0
        self.cache_misses = 0
        self.queue_s: list[float] = []        # per request
        self.run_s: list[float] = []          # per batch
        self.billed_iterations = 0
        self.saved_iterations = 0             # vs every scenario running
                                              # max_it
        self.capped_scenarios = 0             # stopped by a time-budget cap
        self.first_submit_t: float | None = None
        self.last_done_t: float | None = None

    def record_submit(self, t: float) -> None:
        with self._lock:
            self.submitted += 1
            if self.first_submit_t is None:
                self.first_submit_t = t

    def record_reject(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_batch(self, *, n_requests: int, n_scenarios: int,
                     run_s: float, cache_hit: bool, t_done: float) -> None:
        with self._lock:
            self.batches += 1
            self.coalesced.append(n_requests)
            self.occupancy.append(n_scenarios)
            self.run_s.append(run_s)
            if cache_hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
            self.last_done_t = t_done

    def record_request_done(self, *, n_scenarios: int, queue_s: float,
                            billed_iterations: int, saved_iterations: int,
                            capped_scenarios: int) -> None:
        with self._lock:
            self.completed += 1
            self.scenarios_completed += n_scenarios
            self.queue_s.append(queue_s)
            self.billed_iterations += billed_iterations
            self.saved_iterations += saved_iterations
            self.capped_scenarios += capped_scenarios

    def record_failed(self, n: int = 1) -> None:
        with self._lock:
            self.failed += n

    def snapshot(self) -> dict:
        """The ``stats()`` payload: plain data, JSON-serializable."""
        with self._lock:
            lookups = self.cache_hits + self.cache_misses
            span = ((self.last_done_t - self.first_submit_t)
                    if self.first_submit_t is not None
                    and self.last_done_t is not None else 0.0)
            return {
                "requests": {
                    "submitted": self.submitted,
                    "rejected": self.rejected,
                    "completed": self.completed,
                    "failed": self.failed,
                    "in_flight": (self.submitted - self.completed
                                  - self.failed),
                    "scenarios_completed": self.scenarios_completed,
                },
                "batches": {
                    "count": self.batches,
                    "mean_occupancy": _mean(self.occupancy),
                    "max_occupancy": max(self.occupancy, default=0),
                    "mean_requests_coalesced": _mean(self.coalesced),
                },
                "cache": {
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                    "hit_rate": (self.cache_hits / lookups if lookups
                                 else 0.0),
                },
                "latency_s": {
                    "queue_mean": _mean(self.queue_s),
                    "queue_max": max(self.queue_s, default=0.0),
                    "run_mean": _mean(self.run_s),
                    "run_max": max(self.run_s, default=0.0),
                },
                "iterations": {
                    "billed": self.billed_iterations,
                    "saved_vs_max_it": self.saved_iterations,
                    "capped_scenarios": self.capped_scenarios,
                },
                "throughput": {
                    "requests_per_s": (self.completed / span if span > 0
                                       else 0.0),
                    "wall_span_s": span,
                },
            }
