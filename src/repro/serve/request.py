"""Request/response types of the sweep service (DESIGN.md §12).

An :class:`IntegrationRequest` is the service's admission unit: it names a
served integrand family, carries the per-scenario parameters of ONE sweep
(a request may hold several scenarios — e.g. four strikes of one book), the
algorithm configuration, a precision target (``rtol``/``atol``), and an
optional wall-clock ``time_budget_s``.  `SweepService.submit` validates the
combination through ``make_plan`` BEFORE anything touches a device and
raises the one-line `PlanError` on rejection.

A :class:`Ticket` is the caller's handle on an admitted request; its
:meth:`Ticket.result` blocks until the micro-batcher has executed the
request and returns a :class:`RequestResult` with per-scenario estimates
and the billing record (each request pays for its own ``n_it_used``
iterations, not for the batch it rode in).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import numpy as np


@dataclasses.dataclass(frozen=True)
class IntegrationRequest:
    """One integration sweep: a served family, its scenario parameters, and
    the targets the run must meet.

    ``rtol``/``atol`` form the precision target (`StopPolicy` semantics:
    stop once ``sdev <= max(rtol * |mean|, atol)``, never before
    ``min_it``); both 0 means a fixed-length run.  ``time_budget_s`` is the
    wall-clock budget: the service converts it into an iteration-count cap
    from the measured per-iteration cost of this request's compatibility
    class and threads it through the adaptive loop's carry — a hard ceiling
    that wins over ``min_it`` (§12).  ``seed`` pins the request's RNG
    stream: scenario ``j`` draws from ``fold_in(PRNGKey(seed), j)``
    whatever batch the request is coalesced into, so results are invariant
    to micro-batching.

    ``family_kwargs`` (a tuple of ``(name, value)`` pairs, hashable so it
    can join the compatibility key) is forwarded to the family builder —
    e.g. ``(("dim", 6),)`` for a 6-d Gaussian sweep.
    """
    family: str
    params: Any
    rtol: float = 0.0
    atol: float = 0.0
    min_it: int = 2
    time_budget_s: float | None = None
    seed: int = 0
    neval: int = 50_000
    max_it: int = 10
    skip: int = 2
    ninc: int = 128
    alpha: float = 0.5
    beta: float = 0.75
    chunk: int = 16_384
    dtype: str = "float32"
    #: §15 accumulation dtype (None = accumulate in ``dtype``).  Part of the
    #: compatibility key: requests under different precision policies never
    #: coalesce into one program.
    accum_dtype: str | None = None
    backend: str = "auto"           # platform default: pallas-fused on TPU
    interpret: bool | None = None
    tile: int | None = None
    family_kwargs: tuple = ()

    @property
    def has_precision_target(self) -> bool:
        return self.rtol > 0.0 or self.atol > 0.0

    def compat_key(self) -> tuple:
        """The micro-batcher's coalescing key: requests sharing it resolve
        to the same family geometry, algorithm config, backend knobs, and
        stop policy — everything that must agree for their scenarios to run
        as extra lanes of ONE vmapped program.  Seeds and time budgets stay
        per-request (per-scenario keys / caps), so they are NOT part of the
        key."""
        return (self.family, tuple(self.family_kwargs), self.neval,
                self.max_it, self.skip, self.ninc, self.alpha, self.beta,
                self.chunk, self.dtype, self.backend, self.interpret,
                self.tile, self.rtol, self.atol, self.min_it,
                self.accum_dtype)


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """Per-scenario estimates + the billing record of one served request."""
    request_id: int
    family: str
    mean: np.ndarray            # (n,) per-scenario estimates
    sdev: np.ndarray            # (n,)
    chi2_dof: np.ndarray        # (n,)
    n_it_used: np.ndarray       # (n,) iterations each scenario executed —
                                # the billing unit (§12)
    targets: np.ndarray | None  # (n,) analytic values where the family has
                                # them
    met_precision: np.ndarray | None  # (n,) bool, None w/o a precision
                                      # target
    it_cap: np.ndarray          # (n,) the iteration cap applied (max_it
                                # when unbounded)
    capped: bool                # any scenario stopped by its time budget
    budget_enforced: bool       # a cost estimate existed, so the cap is
                                # derived from the budget (False on the
                                # calibration batch of a new class)
    billed_iterations: int      # sum(n_it_used) — what this request pays
    billed_evals: int           # billed_iterations * neval (approximate)
    queue_s: float              # submit -> batch execution start
    run_s: float                # the batch's wall clock (shared by every
                                # request coalesced into it)
    batch_id: int
    batch_size: int             # scenarios in the batch this request rode
    warm_started: bool          # maps seeded from the shared MapCache pool

    @property
    def n_scenarios(self) -> int:
        return int(self.mean.shape[0])

    def __repr__(self):
        ok = ("-" if self.met_precision is None
              else f"{int(self.met_precision.sum())}/{self.n_scenarios}")
        return (f"RequestResult(id={self.request_id}, family={self.family}, "
                f"n={self.n_scenarios}, met_precision={ok}, "
                f"billed_it={self.billed_iterations}, "
                f"queue={self.queue_s * 1e3:.1f}ms, "
                f"run={self.run_s * 1e3:.1f}ms)")


class Ticket:
    """Caller-side handle on an admitted request (thread-safe)."""

    def __init__(self, request: IntegrationRequest, request_id: int,
                 family, params: np.ndarray, t_submit: float):
        self.request = request
        self.request_id = request_id
        self.compat_key = request.compat_key()
        self.family = family          # the admission-built IntegrandFamily
        self.params = params          # normalized builder-input params
        self.t_submit = t_submit
        self._event = threading.Event()
        self._result: RequestResult | None = None
        self._error: BaseException | None = None

    @property
    def n_scenarios(self) -> int:
        return int(self.family.batch_size)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> RequestResult:
        """Block until the micro-batcher has executed this request."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not served within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def _resolve(self, result: RequestResult) -> None:
        self._result = result
        self._event.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._event.set()
