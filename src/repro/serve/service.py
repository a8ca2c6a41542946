"""Integration-as-a-service: the queued sweep service (DESIGN.md §12).

`SweepService` multiplexes many integration requests onto shared compute —
the serving layer the ROADMAP's "millions of users" north star asks for,
composed entirely from engine pieces PRs 1–6 built:

  * **admission** — `submit` resolves each request into a (family,
    VegasConfig, ExecutionConfig) combination and validates it with
    ``make_plan`` BEFORE it can touch a device; invalid combinations are
    rejected with the engine's one-line `PlanError`;
  * **micro-batching** — queued requests sharing a compatibility key (same
    family geometry + resolved config + stop policy) coalesce into ONE
    vmapped whole-run program (`engine.executor.make_family_program`) with
    per-scenario stop masks; the compiled program is cached per class, so
    a burst pays trace+compile once, not per request;
  * **warm starts** — importance maps are seeded from a shared
    `batch.cache.MapCache`: the service pools one scenario-averaged map per
    (family, config) class — stored under a batch-size-1 pool key so a hit
    broadcasts to any occupancy — and refreshes it after every batch;
  * **time budgets** — a request's wall-clock budget becomes an
    iteration-count cap (``floor(budget / measured per-iteration cost)``)
    threaded through the adaptive loop's carry (`core.run_loop`); the cost
    model is the engine's shared `engine.autotune.OnlineCost`: min-observed
    per compatibility class from executed batches (the first batch of a
    class calibrates, subsequent ones enforce), optionally seeded with a
    calibrated `CostTable` prior so even a class's first batch is enforced;
  * **billing** — every request pays for its own scenarios' ``n_it_used``,
    not for the batch it rode in;
  * **metrics** — queue/run latency, batch occupancy, cache hit rate, and
    iterations saved, exposed by :meth:`SweepService.stats`.

The service is in-process: drive it synchronously with :meth:`drain`
(tests) or start the background worker thread
(:meth:`start`/:meth:`stop`) that gathers each burst for ``max_wait_s``
and executes it — the long-lived form the `repro.launch.serve` CLI runs.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.batch.cache import MapCache
from repro.batch.engine import scenario_keys
from repro.batch.family import (IntegrandFamily, make_asian_family,
                                make_gaussian_family, make_ridge_family)
from repro.core import integrator as core
from repro.engine import (ExecutionConfig, PlanError, PrecisionPolicy,
                          StopPolicy, make_plan)
from repro.engine import autotune as autotune_mod
from repro.engine import executor as executor_mod

from .metrics import ServeMetrics
from .request import IntegrationRequest, RequestResult, Ticket


@dataclasses.dataclass(frozen=True)
class ServedFamily:
    """A servable integrand family: how to normalize request params and
    build the (possibly coalesced) `IntegrandFamily` from them.
    ``normalize(params, dtype)`` receives the REQUEST's dtype — params must
    come back in it, or the family's vmapped closure constants silently
    promote the whole fill to float64 (the §15 dtype-correctness audit)."""
    name: str
    build: Callable[..., IntegrandFamily]
    normalize: Callable[..., np.ndarray]


def _norm_1d(params, dtype=np.float64) -> np.ndarray:
    return np.atleast_1d(np.asarray(params, dtype))


def _norm_2d(params, dtype=np.float64) -> np.ndarray:
    return np.atleast_2d(np.asarray(params, dtype))


#: The default serving registry: family name -> builder taking ONE
#: positional per-scenario parameter array (scenario axis leading), so the
#: micro-batcher can concatenate requests' params and rebuild.
SERVED_FAMILIES: dict[str, ServedFamily] = {
    "gaussian": ServedFamily("gaussian", make_gaussian_family, _norm_1d),
    "asian": ServedFamily("asian", make_asian_family, _norm_1d),
    "ridge": ServedFamily("ridge", make_ridge_family, _norm_2d),
}


class _PoolKey:
    """Duck-typed (name, batch_size) pair for `batch.cache.cache_key`: the
    service's map pool stores ONE scenario-averaged map per (family,
    config) class under batch size 1, so a hit broadcasts to any
    occupancy."""

    def __init__(self, family_name: str):
        self.name = f"{family_name}@serve-pool"
        self.batch_size = 1


class SweepService:
    """Long-lived queued sweep service over `repro.engine` (§12).

    ``max_batch`` bounds scenarios per coalesced program; ``max_wait_s`` is
    the background worker's micro-batching window (how long the first
    request of a burst waits for companions); ``cache`` shares warm maps —
    a `MapCache`, a path (persistent, shareable with CLI sweeps), or None
    for a private in-memory pool.

    ``cost_table`` seeds the budget cost model with the engine's shared
    calibrated table (`engine.autotune.CostTable` or a path): classes with
    no executed batch yet fall back to the table's predicted
    per-scenario-iteration cost, so a request's FIRST batch can already be
    budget-enforced.  ``None`` (the default) keeps the legacy behavior —
    the first batch of each class calibrates, measured minima enforce from
    the second on — bit-identical results either way (`OnlineCost`).
    """

    def __init__(self, *, max_batch: int = 16, max_wait_s: float = 0.02,
                 cache: MapCache | str | None = None,
                 families: dict[str, ServedFamily] | None = None,
                 max_programs: int = 32,
                 cost_table: "autotune_mod.CostTable | str | None" = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self.families = dict(SERVED_FAMILIES if families is None
                             else families)
        self.cache = (MapCache(cache) if isinstance(cache, str)
                      else (cache if cache is not None else MapCache()))
        self.metrics = ServeMetrics()
        self._cv = threading.Condition()
        self._pending: list[Ticket] = []
        self._stopping = False
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()        # programs + cost model
        self._programs: OrderedDict[tuple, Any] = OrderedDict()
        self._max_programs = max_programs
        if isinstance(cost_table, str):
            cost_table = autotune_mod.CostTable.load(cost_table)
        # The engine's shared cost model (§13): min-observed per-class
        # per-scenario-iteration seconds, with the table as prior.
        self._cost = autotune_mod.OnlineCost(table=cost_table)
        self._ids = iter(range(1 << 62))
        self._batch_ids = iter(range(1 << 62))

    # --- admission -----------------------------------------------------------

    def _resolve(self, request: IntegrationRequest):
        """Request -> (family, VegasConfig); raises PlanError on anything
        the service cannot serve (before make_plan sees it)."""
        spec = self.families.get(request.family)
        if spec is None:
            raise PlanError(
                f"unknown served family {request.family!r}; served: "
                f"{sorted(self.families)}")
        try:
            # Normalize INTO the request's dtype: a float64 param array
            # closed over by the family would otherwise promote every
            # sample/product in the fill to f64 behind the plan's back.
            params = spec.normalize(request.params, np.dtype(request.dtype))
        except Exception as e:
            raise PlanError(
                f"family {request.family!r} params not normalizable: "
                f"{e}") from None
        if params.shape[0] == 0:
            raise PlanError("request carries zero scenarios")
        if (request.time_budget_s is not None
                and not request.time_budget_s > 0):
            raise PlanError(
                f"time_budget_s must be positive, got "
                f"{request.time_budget_s}")
        try:
            family = spec.build(params, **dict(request.family_kwargs))
        except Exception as e:
            raise PlanError(
                f"family {request.family!r} rejected "
                f"kwargs={dict(request.family_kwargs)}: {e}") from None
        stop = (StopPolicy(rtol=request.rtol, atol=request.atol,
                           min_it=request.min_it)
                if (request.rtol != 0 or request.atol != 0) else None)
        precision = (PrecisionPolicy(accum_dtype=request.accum_dtype)
                     if request.accum_dtype else None)
        execution = ExecutionConfig(
            backend=request.backend, interpret=request.interpret,
            tile=request.tile, batch="vmap", stop=stop,
            precision=precision)
        cfg = core.VegasConfig(
            neval=request.neval, max_it=request.max_it, skip=request.skip,
            ninc=request.ninc, alpha=request.alpha, beta=request.beta,
            chunk=request.chunk, dtype=request.dtype, execution=execution)
        return family, params, cfg

    def submit(self, request: IntegrationRequest) -> Ticket:
        """Admit one request: plan-validate it (admission control — a
        `PlanError` here has touched no device) and enqueue it for the
        micro-batcher.  Returns the caller's :class:`Ticket`."""
        t = time.perf_counter()
        try:
            family, params, cfg = self._resolve(request)
            make_plan(family, cfg)     # the admission check (PlanError)
        except PlanError:
            self.metrics.record_reject()
            raise
        ticket = Ticket(request, next(self._ids), family, params, t)
        self.metrics.record_submit(t)
        with self._cv:
            self._pending.append(ticket)
            self._cv.notify_all()
        return ticket

    # --- the micro-batcher ---------------------------------------------------

    def _take_pending(self) -> list[Ticket]:
        with self._cv:
            pending, self._pending = self._pending, []
        return pending

    def _group(self, pending: list[Ticket]) -> list[list[Ticket]]:
        """FIFO greedy coalescing: same compat key, up to ``max_batch``
        scenarios per batch; a request is never split (one larger than
        max_batch forms its own batch)."""
        by_key: OrderedDict[tuple, list[Ticket]] = OrderedDict()
        for t in pending:
            by_key.setdefault(t.compat_key, []).append(t)
        batches = []
        for tickets in by_key.values():
            cur: list[Ticket] = []
            cur_n = 0
            for t in tickets:
                if cur and cur_n + t.n_scenarios > self.max_batch:
                    batches.append(cur)
                    cur, cur_n = [], 0
                cur.append(t)
                cur_n += t.n_scenarios
            if cur:
                batches.append(cur)
        return batches

    def drain(self) -> int:
        """Execute everything queued right now, in the calling thread.
        Returns the number of micro-batches run."""
        pending = self._take_pending()
        if not pending:
            return 0
        batches = self._group(pending)
        for tickets in batches:
            try:
                self._run_batch(tickets)
            except Exception as e:
                self.metrics.record_failed(len(tickets))
                for t in tickets:
                    t._fail(e)
        return len(batches)

    def _program(self, key: tuple, plan):
        """The per-class compiled-program cache (LRU).  One jitted callable
        per compatibility class serves every batch size (jit retraces per
        B, reuses per shape) — a burst pays trace+compile once."""
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                return prog
        prog = executor_mod.make_family_program(plan, with_caps=True)
        with self._lock:
            self._programs[key] = prog
            self._programs.move_to_end(key)
            while len(self._programs) > self._max_programs:
                self._programs.popitem(last=False)
        return prog

    def _caps_for(self, tickets: list[Ticket], rcfg,
                  batch_scenarios: int) -> tuple[np.ndarray, bool]:
        """Per-scenario iteration caps from each request's time budget and
        the class's per-iteration cost — the min-observed measurement, or
        the shared `CostTable` prediction for a class with no executed
        batch yet (`OnlineCost.unit`).  Returns ``(caps (B,), enforced)`` —
        ``enforced`` False while the class is uncalibrated AND no table
        prior exists (first batch), in which case every cap is ``max_it``."""
        req0 = tickets[0].request
        max_it = rcfg.max_it
        with self._lock:
            unit = self._cost.unit(tickets[0].compat_key, rcfg=rcfg,
                                   backend=rcfg.execution.backend,
                                   interpret=req0.interpret, tile=req0.tile)
        caps, enforced = [], unit is not None
        for t in tickets:
            budget = t.request.time_budget_s
            if budget is None or unit is None:
                cap = max_it
            else:
                # The whole batch shares one wall clock: an iteration of
                # the batch costs ~unit * B, and the request's budget must
                # cover the iterations IT runs.
                cap = int(budget / (unit * batch_scenarios))
                cap = max(1, min(max_it, cap))
            caps.extend([cap] * t.n_scenarios)
        return np.asarray(caps, np.int32), enforced

    def _run_batch(self, tickets: list[Ticket]) -> None:
        """Execute one coalesced micro-batch and bill its requests."""
        t_start = time.perf_counter()
        req0 = tickets[0].request
        params = np.concatenate([t.params for t in tickets], axis=0)
        family, _, cfg = self._resolve(
            dataclasses.replace(req0, params=params))
        plan = make_plan(family, cfg)
        rcfg = plan.cfg
        b = plan.batch_size

        # Every request keeps its own stream: scenario j of request r draws
        # from fold_in(PRNGKey(r.seed), j) — invariant to coalescing.
        keys = jnp.concatenate(
            [scenario_keys(jax.random.PRNGKey(t.request.seed),
                           t.n_scenarios) for t in tickets], axis=0)
        caps, enforced = self._caps_for(tickets, rcfg, b)

        # Warm start from the shared map pool (batch-size-independent).
        pool_key = _PoolKey(family.name)
        pooled = self.cache.get(pool_key, rcfg)
        warm = pooled is not None
        edges0 = (jnp.broadcast_to(pooled, (b,) + pooled.shape[1:])
                  if warm
                  else executor_mod.uniform_family_edges(family, rcfg, b))

        prog = self._program(tickets[0].compat_key, plan)
        states, mean, sdev, chi2_dof, n_used = prog(
            family.params, keys, edges0, jnp.asarray(caps))
        res = executor_mod.package_batch_result(
            states, mean, sdev, chi2_dof, n_used, warm_started=warm)
        t_done = time.perf_counter()
        run_s = t_done - t_start

        # Cost model update: wall / (trips * B) approximates the
        # per-scenario-iteration cost; `OnlineCost.observe` keeps the
        # MINIMUM observed so trace+compile-inflated samples (the
        # calibration batch) never poison the estimate upward.
        trips = max(int(res.n_it_used.max()), 1)
        with self._lock:
            self._cost.observe(tickets[0].compat_key, run_s / (trips * b))

        # Refresh the pool with the scenario-averaged converged map.
        self.cache.put(pool_key, rcfg,
                       np.asarray(res.states.edges).mean(axis=0,
                                                         keepdims=True))

        batch_id = next(self._batch_ids)
        self.metrics.record_batch(
            n_requests=len(tickets), n_scenarios=b, run_s=run_s,
            cache_hit=warm, t_done=t_done)
        self._bill(tickets, res, caps, enforced, rcfg, run_s, t_start,
                   batch_id, b)

    def _bill(self, tickets, res, caps, enforced, rcfg, run_s, t_start,
              batch_id, batch_size) -> None:
        lo = 0
        for t in tickets:
            hi = lo + t.n_scenarios
            mean = res.mean[lo:hi]
            sdev = res.sdev[lo:hi]
            n_it = res.n_it_used[lo:hi]
            cap = caps[lo:hi]
            req = t.request
            met = None
            if req.has_precision_target:
                target = np.maximum(req.rtol * np.abs(mean), req.atol)
                met = sdev <= target
            billed = int(n_it.sum())
            result = RequestResult(
                request_id=t.request_id, family=req.family, mean=mean,
                sdev=sdev, chi2_dof=res.chi2_dof[lo:hi],
                n_it_used=n_it.astype(np.int64),
                targets=(None if t.family.targets is None
                         else np.asarray(t.family.targets)),
                met_precision=met, it_cap=cap.astype(np.int64),
                capped=bool((n_it >= cap).any() and (cap < rcfg.max_it).any()),
                budget_enforced=(enforced
                                 and req.time_budget_s is not None),
                billed_iterations=billed,
                billed_evals=billed * req.neval,
                queue_s=t_start - t.t_submit, run_s=run_s,
                batch_id=batch_id, batch_size=batch_size,
                warm_started=res.warm_started)
            self.metrics.record_request_done(
                n_scenarios=t.n_scenarios, queue_s=result.queue_s,
                billed_iterations=billed,
                saved_iterations=t.n_scenarios * rcfg.max_it - billed,
                capped_scenarios=int(((n_it >= cap)
                                      & (cap < rcfg.max_it)).sum()))
            t._resolve(result)
            lo = hi

    # --- the long-lived worker -----------------------------------------------

    def start(self) -> "SweepService":
        """Start the background worker: gathers each burst for
        ``max_wait_s`` (the micro-batching window) and drains it."""
        with self._cv:
            if self._thread is not None:
                return self
            self._stopping = False
        self._thread = threading.Thread(target=self._worker,
                                        name="sweep-service", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Drain outstanding work and stop the worker."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.drain()   # anything submitted after the worker exited

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stopping:
                    self._cv.wait()
                if not self._pending and self._stopping:
                    return
            if self.max_wait_s > 0:
                time.sleep(self.max_wait_s)   # let the burst arrive
            self.drain()

    def __enter__(self) -> "SweepService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # --- observability -------------------------------------------------------

    def stats(self) -> dict:
        """The metrics endpoint: request/batch/cache/latency/billing
        aggregates (`ServeMetrics.snapshot`) plus the live cost model."""
        snap = self.metrics.snapshot()
        with self._lock:
            snap["cost_model"] = {
                "classes_calibrated": self._cost.classes_calibrated,
                "per_scenario_iteration_s": self._cost.snapshot(),
                "table": (None if self._cost.table is None
                          else self._cost.table.source),
            }
            snap["programs_cached"] = len(self._programs)
        return snap
