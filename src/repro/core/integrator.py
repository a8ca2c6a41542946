"""VEGAS+ driver: iterate fill -> adapt -> aggregate (paper Alg. 1).

The whole iteration (fill, stratification update, map update, estimate) is a
single jitted program — the JAX realization of cuVegas' "everything stays on
device" design (C4/C6): there are no host transfers inside an iteration, and
XLA overlaps the map update with result aggregation (the paper used two CUDA
streams for this).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.engine.config import LEGACY_EXEC_FIELDS, ExecutionConfig

from . import fill as fill_mod
from . import map as vmap_
from . import strat
from .integrands import Integrand

_ALGO_FIELDS = (
    ("neval", 100_000),       # target integrand evaluations / iteration
    ("max_it", 20),           # max_it
    ("skip", 0),              # iterations excluded from the final combine
    ("ninc", 1024),           # n_intervals of the importance map
    ("alpha", 0.5),           # importance-map damping
    ("beta", 0.75),           # stratification damping (0 => classic VEGAS)
    ("nstrat", None),         # stratifications/dim (None => heuristic)
    ("max_cubes", 1 << 18),   # cap on nstrat**d
    ("chunk", 16_384),        # evals per scanned chunk (batch_size analog)
    ("dtype", "float32"),
)


@dataclasses.dataclass(frozen=True, init=False)
class VegasConfig:
    """Algorithm parameters (paper Table 2 names where they exist) plus ONE
    execution handle: ``execution`` (`repro.engine.ExecutionConfig`) carries
    everything about HOW the run executes — backend, kernel knobs, batching,
    sharding, checkpointing (DESIGN.md §9).

    Deprecation shim: the pre-engine flat fields (``backend``, ``interpret``,
    ``fused_cubes``, ``tile``) are still accepted as keyword arguments (with
    a DeprecationWarning) and folded into ``execution``; reading them back
    (``cfg.backend`` etc.) keeps working via properties.
    """
    neval: int = 100_000
    max_it: int = 20
    skip: int = 0
    ninc: int = 1024
    alpha: float = 0.5
    beta: float = 0.75
    nstrat: int | None = None
    max_cubes: int = 1 << 18
    chunk: int = 16_384
    dtype: str = "float32"
    execution: ExecutionConfig = ExecutionConfig()

    def __init__(self, *args, execution: ExecutionConfig | None = None,
                 **kwargs):
        names = [n for n, _ in _ALGO_FIELDS]
        if len(args) > len(names):
            raise TypeError(f"VegasConfig takes at most {len(names)} "
                            f"positional arguments ({len(args)} given)")
        vals = dict(_ALGO_FIELDS)
        positional = dict(zip(names, args))
        vals.update(positional)
        legacy = {}
        for k, v in kwargs.items():
            if k in positional:
                raise TypeError(f"duplicate argument {k!r}")
            if k in vals:
                vals[k] = v
            elif k in LEGACY_EXEC_FIELDS:
                legacy[k] = v
            else:
                raise TypeError(f"unexpected argument {k!r}")
        if legacy:
            warnings.warn(
                f"VegasConfig({', '.join(sorted(legacy))}) is deprecated: "
                f"execution knobs moved to "
                f"VegasConfig(execution=ExecutionConfig(...))",
                DeprecationWarning, stacklevel=2)
            execution = (execution or ExecutionConfig()).with_legacy(**legacy)
        for k, v in vals.items():
            object.__setattr__(self, k, v)
        object.__setattr__(self, "execution", execution or ExecutionConfig())

    # Read-side back-compat for the old flat fields.
    @property
    def backend(self) -> str:
        return self.execution.backend

    @property
    def interpret(self) -> bool | None:
        return self.execution.interpret

    @property
    def fused_cubes(self) -> bool:
        return self.execution.backend == "pallas-fused"

    @property
    def tile(self) -> int | None:
        return self.execution.tile

    def with_execution(self, execution: ExecutionConfig) -> "VegasConfig":
        return dataclasses.replace(self, execution=execution)

    def resolve(self, dim: int) -> "ResolvedConfig":
        ns = self.nstrat or strat.choose_nstrat(self.neval, dim, self.max_cubes)
        n_cubes = ns**dim
        n_cap = strat.eval_capacity(self.neval, n_cubes)
        chunk = min(self.chunk, max(n_cap, 256))
        n_cap = ((n_cap + chunk - 1) // chunk) * chunk  # pad to chunk multiple
        return ResolvedConfig(self, dim, ns, n_cubes, n_cap, chunk)


@dataclasses.dataclass(frozen=True)
class ResolvedConfig:
    base: VegasConfig
    dim: int
    nstrat: int
    n_cubes: int
    n_cap: int
    chunk: int

    def __getattr__(self, name):
        return getattr(self.base, name)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VegasState:
    """Everything the algorithm carries across iterations. O(KB): this is the
    checkpoint payload for fault-tolerant runs (DESIGN.md §5)."""
    edges: jax.Array      # (d, ninc+1) importance map
    n_h: jax.Array        # (n_cubes,) evals per hypercube
    key: jax.Array        # base PRNG key
    it: jax.Array         # iteration counter
    results: jax.Array    # (max_it, 2): per-iteration (I_i, sigma2_i)

    def tree_flatten(self):
        return (self.edges, self.n_h, self.key, self.it, self.results), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class VegasResult:
    mean: float
    sdev: float
    chi2_dof: float
    n_it: int             # iterations entering the combination (n_used)
    iter_means: jax.Array
    iter_sdevs: jax.Array
    state: VegasState
    n_it_used: int = 0    # iterations actually executed (< max_it when a
                          # StopPolicy converged the run early, §10)

    def __repr__(self):
        return (f"VegasResult(mean={self.mean:.8g}, sdev={self.sdev:.3g}, "
                f"chi2/dof={self.chi2_dof:.2f}, n_it={self.n_it}, "
                f"n_it_used={self.n_it_used})")


def init_state(integrand: Integrand, cfg: ResolvedConfig, key) -> VegasState:
    dtype = jnp.dtype(cfg.dtype)
    edges = vmap_.uniform_edges(integrand.lower, integrand.upper, cfg.ninc, dtype)
    n_h = strat.uniform_nh(cfg.neval, cfg.n_cubes)
    results = jnp.stack([jnp.zeros((cfg.max_it,), dtype),
                         jnp.full((cfg.max_it,), jnp.inf, dtype)], axis=1)
    return VegasState(edges, n_h, key, jnp.zeros((), jnp.int32), results)


def iteration_step(state: VegasState, integrand: Integrand,
                   cfg: ResolvedConfig, fill_fn=None) -> VegasState:
    """One VEGAS+ iteration. ``fill_fn`` lets the engine (or a custom
    caller) substitute the fill — e.g. the shard_mapped multi-device fill —
    while reusing adaptation/aggregation unchanged.  The default comes from
    the capability-declaring backend registry (`repro.engine.backends`)."""
    dtype = jnp.dtype(cfg.dtype)
    key_it = jax.random.fold_in(state.key, state.it)
    if fill_fn is None:
        from repro.engine import backends as _backends
        fill_fn = _backends.bind_fill(cfg)
    res = fill_fn(state.edges, state.n_h, key_it, integrand)

    i_it, sigma2_it, d_h = fill_mod.estimate_from_cubes(res, state.n_h)
    results = state.results.at[state.it].set(
        jnp.stack([i_it.astype(dtype), sigma2_it.astype(dtype)]))

    # Adaptive stratification (the "+" of VEGAS+); beta=0 freezes n_h uniform.
    n_h = (strat.adapt_nh(d_h, cfg.beta, cfg.neval)
           if cfg.beta > 0 else state.n_h)
    # Importance-map adaptation; alpha=0 freezes the map.  Widened (§15)
    # moments would promote the adapted edges to the accum dtype — cast back
    # so the loop-carried state (and next iteration's samples) stay in the
    # sample dtype.
    edges = (vmap_.adapt_edges(state.edges, res.map_sums, res.map_counts,
                               cfg.alpha).astype(dtype)
             if cfg.alpha > 0 else state.edges)
    return VegasState(edges, n_h, state.key, state.it + 1, results)


def combine_results(results: jax.Array, skip: int, n_done: int):
    """Inverse-variance weighted combination across iterations (eq. (8)-(9))
    plus the chi^2/dof consistency diagnostic vegas reports.

    Sentinel contract (§10): the results buffer is always fixed-shape
    ``(max_it, 2)``; iterations the loop never executed keep the
    ``(0.0, inf)`` fill from ``init_state``.  Slots with index ``>= n_done``
    are excluded by the explicit ``idx < n_done`` mask — and even if a slot
    past ``n_done`` held finite garbage it could not leak in — while the
    ``isfinite`` guard independently drops the inf sentinels, so the stats
    ignore unfilled slots for every ``n_done < max_it``
    (tests/test_early_stop.py proves both properties).  ``n_done`` may be a
    traced scalar (the adaptive while_loop evaluates this every iteration).

    Degenerate case: when no iteration is usable (every sig2 is inf or
    non-finite, so ``wsum == 0``) the result is the NaN-free sentinel
    ``(0.0, inf, 0.0, 0)`` — zero information, not a silent NaN.

    Differentiation contract (§11): every consumer that differentiates
    through this function (the grad module's running-stat paths, user code
    taking ``jax.grad`` of a combined estimate) relies on the double-where
    idiom below: each ``1/x`` whose operand can be the 0-or-inf sentinel is
    guarded INSIDE its selecting ``where``, so the unused branch never
    produces the ``0 * inf = NaN`` that reverse-mode would otherwise
    propagate into the gradients of early-stopped runs (whose results
    buffer keeps ``(0.0, inf)`` sentinel rows past ``n_done``).
    tests/test_grad.py::test_combine_results_grad_nan_safe is the
    regression.
    """
    means, sig2 = results[:, 0], results[:, 1]
    idx = jnp.arange(results.shape[0])
    use = (idx >= skip) & (idx < n_done) & jnp.isfinite(sig2) & (sig2 > 0)
    wts = jnp.where(use, 1.0 / jnp.where(use, sig2, 1.0), 0.0)
    wsum = jnp.sum(wts)
    any_used = wsum > 0
    mean = jnp.where(any_used,
                     jnp.sum(wts * means) / jnp.where(any_used, wsum, 1.0), 0.0)
    # inf when nothing was usable — via the guarded branch, NOT a bare
    # 1/wsum: d(1/wsum) at wsum=0 is -inf, and inf * the zero cotangent of
    # the unselected branch would NaN-poison grads of early-stopped runs.
    var = jnp.where(any_used, 1.0 / jnp.where(any_used, wsum, 1.0), jnp.inf)
    n_used = jnp.sum(use)
    chi2 = jnp.sum(jnp.where(use, wts * (means - mean) ** 2, 0.0))
    chi2_dof = jnp.where(any_used, chi2 / jnp.maximum(n_used - 1, 1), 0.0)
    return mean, jnp.sqrt(var), chi2_dof, n_used


def run_loop(state: VegasState, integrand: Integrand, cfg: ResolvedConfig,
             start: int, fill_fn=None, *, stop=None,
             stop_sync=None, it_cap=None) -> VegasState:
    """The ADAPT phase: the whole iteration loop as one traced program.

    Fixed-length mode (no active stop policy): ``lax.fori_loop`` over
    :func:`iteration_step` from ``start`` to ``cfg.max_it``.  This is the
    jitted single-program path of ``run`` (no host sync between iterations,
    DESIGN.md B1) and the unit the batch engine ``vmap``s over scenarios
    (``repro.batch.engine``).  ``iteration_step`` keys its RNG and results
    slot off ``state.it``, so looping over it is bit-identical to stepping
    it from a host loop (checked by tests/test_determinism.py).

    Adaptive mode (``stop`` is an active `repro.engine.StopPolicy`, §10):
    the same ``iteration_step`` under a fixed-shape ``lax.while_loop``.  The
    carry is ``(state, running stats, continue?)`` where the running
    ``(mean, sdev, chi2_dof)`` are re-derived from the results buffer by
    :func:`combine_results` after every iteration; the loop exits once the
    combined sdev meets ``max(rtol * |mean|, atol)`` (never before
    ``stop.min_it``) or ``max_it`` is reached.  Nothing about the state's
    shape changes — the ``(max_it, 2)`` buffer keeps its ``sigma2 = inf``
    sentinels past ``state.it`` — so the program stays jittable, resumes
    from fixed-loop checkpoints (the running stats are a pure function of
    the carried results buffer, so a resume re-derives them exactly), and
    ``vmap``s: under the while_loop batching rule, scenarios whose predicate
    went false keep their old carry via ``select`` — converged lanes become
    no-op iterations while stragglers continue, one shared trace.

    ``stop_sync`` (optional) reduces the per-iteration continue decision
    across mesh axes when the loop itself runs inside a ``shard_map``
    (`engine.sharding.make_stop_sync`): every shard computes the identical
    replicated statistics, and the explicit all-agree reduction guarantees
    the loop cannot diverge across devices.

    ``it_cap`` (optional, §12) is the time-budget stopping input: a traced
    iteration-count cap — the serving layer derives it from a request's
    wall-clock budget and the measured per-iteration cost.  It rides the
    while_loop carry next to the running stats, so the loop exits at
    ``it >= min(max_it, it_cap)`` even when no precision target is set (a
    budget-only run still uses the while_loop), and under ``vmap`` a
    per-scenario cap array gives every lane its own budget.  The cap is a
    HARD ceiling: it wins over ``min_it`` (a spent budget must stop the run
    even if the policy would rather keep adapting).
    """
    if stop is None:
        stop = getattr(cfg.execution, "stop", None)
    if stop is not None and not stop.active:
        stop = None
    if stop is None and it_cap is None:
        return jax.lax.fori_loop(
            start, cfg.max_it,
            lambda _, s: iteration_step(s, integrand, cfg, fill_fn), state)

    def running_stats(s):
        mean, sdev, chi2_dof, _ = combine_results(s.results, cfg.skip, s.it)
        return mean, sdev, chi2_dof

    def wants_more(s, stats, cap):
        mean, sdev, _ = stats
        cont = s.it < jnp.minimum(cfg.max_it, cap)
        if stop is not None:
            cont = cont & ~stop.converged(mean, sdev, s.it)
        if stop_sync is not None:
            cont = stop_sync(cont)
        return cont

    # The running stats and the iteration cap ride the carry next to the
    # continue flag: cond reads only the flag (the decision is made in the
    # body, where stop_sync can psum it), while the carried (mean, sdev,
    # chi2_dof) keep the §10 contract that the stop statistics live
    # alongside the state — inspectable mid-loop and re-derivable on resume.
    cap = jnp.asarray(cfg.max_it if it_cap is None else it_cap, jnp.int32)

    def stop_test(s, cap):
        with obs.scope("vegas.stop"):
            stats = running_stats(s)
            return stats, wants_more(s, stats, cap)

    def body(carry):
        s, _, cap, _ = carry
        s = iteration_step(s, integrand, cfg, fill_fn)
        stats, cont = stop_test(s, cap)
        return s, stats, cap, cont

    stats0, cont0 = stop_test(state, cap)
    carry = (state, stats0, cap, cont0)
    state, _, _, _ = jax.lax.while_loop(lambda c: c[3], body, carry)
    return state


#: The two-phase split (§11): ``adapt_loop`` is `run_loop` under its phase
#: name — the part of a differentiable run that executes with gradients
#: stopped — and :func:`eval_phase` is the frozen-map pass whose pathwise
#: gradient is exact Monte Carlo.
adapt_loop = run_loop


def eval_key(key, cfg: ResolvedConfig):
    """RNG key of the frozen-map evaluation pass: ``fold_in(key, max_it)``.

    Adapt iterations consume ``fold_in(key, it)`` for ``it < max_it``
    (`iteration_step`), so the ``max_it`` slot is never drawn by the adapt
    phase — the eval pass gets a deterministic stream independent of every
    adapt iteration, whether or not a StopPolicy truncated the loop.
    """
    return jax.random.fold_in(key, cfg.max_it)


def eval_phase(edges, n_h, integrand: Integrand, cfg: ResolvedConfig, key,
               fill_fn=None):
    """The EVAL phase of a two-phase run (§11): one fill over a FROZEN map.

    ``edges``/``n_h`` are the converged (and, in a differentiable run,
    ``stop_gradient``-frozen) map and stratification; the pass neither
    adapts nor touches the results buffer.  Returns the pass's
    ``(estimate, sigma2)`` from :func:`fill.estimate_from_cubes` — for a
    fixed map this is an unbiased estimate of the integral whatever the
    map, which is exactly why the adapt phase's parameter-dependence can be
    dropped from the gradient (DESIGN.md §11).  Pure jnp when ``fill_fn``
    binds the ``ref`` backend, hence differentiable w.r.t. anything the
    integrand or ``edges`` carry (`repro.grad` builds on this).
    """
    if fill_fn is None:
        from repro.engine import backends as _backends
        fill_fn = _backends.bind_fill(cfg)
    res = fill_fn(edges, n_h, key, integrand)
    i_ev, sigma2_ev, _ = fill_mod.estimate_from_cubes(res, n_h)
    return i_ev, sigma2_ev


def run(integrand: Integrand, cfg: VegasConfig | None = None, *,
        key=None, fill_fn=None, state: VegasState | None = None,
        checkpoint_cb: Callable[[int, VegasState], None] | None = None) -> VegasResult:
    """Run VEGAS+ to completion (or resume from ``state``).

    Thin adapter over the execution engine: ``make_plan`` validates the
    config's execution axes (backend/sharding/checkpoint/stop,
    `repro.engine`) and ``execute`` runs the plan.  With no checkpoint
    policy the whole loop executes as a single jitted on-device program
    (``run_loop``): zero host round-trips between iterations.  An active
    ``ExecutionConfig(stop=StopPolicy(...))`` ends the loop as soon as the
    combined sdev meets the target — ``VegasResult.n_it_used`` reports how
    many iterations actually ran (§10).

    Legacy extension hooks, forwarded to the executor unchanged:
    ``fill_fn(edges, n_h, key_it, integrand) -> FillResult`` replaces the
    plan's fill wiring entirely (prefer ``ExecutionConfig(mesh=...)``);
    ``checkpoint_cb(it, state)`` forces the host-side loop and is invoked
    after every iteration (prefer ``ExecutionConfig(checkpoint=
    CheckpointPolicy(...))``).  Resume by passing the restored ``state``
    (the results buffer grows automatically if the resuming config has a
    larger ``max_it``).
    """
    from repro.engine import execute, make_plan
    with obs.span("repro.run"):
        plan = make_plan(integrand, cfg)
        return execute(plan, key=key, state=state, fill_fn=fill_fn,
                       checkpoint_cb=checkpoint_cb)
