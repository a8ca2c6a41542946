"""Executor: run a validated :class:`~repro.engine.plan.Plan`.

One entry point, :func:`execute`, composes the plan axes into a single
program per run:

  * **single scenario** — `core.run_loop` as one jitted ``fori_loop``
    program (or the host loop when a checkpoint policy is set), built once
    per plan and kept in a bounded process-wide cache, so a repeated
    ``core.run`` of one integrand and config reuses it (DESIGN.md §9);
  * **batched family**  — the whole loop ``vmap``ped over the scenario axis
    (`repro.batch` semantics: scenario ``b`` streams from ``fold_in(key,
    b)``, so batched == serial stream-for-stream);
  * **sharded**         — the fill's chunk axis divided over the mesh.  For
    single runs the fill call is shard_mapped; for batched runs the ENTIRE
    vmapped program runs inside one ``shard_map`` with the per-shard fill +
    psum inline — B integrands × D devices as ONE jitted XLA program, the
    combination the pre-engine run paths could not express;
  * **checkpointing**   — the policy's callback after every iteration on the
    host-loop path, composing with sharding (mesh-free payload, §5);
  * **stopping**        — an active `StopPolicy` swaps the fori_loop for the
    fixed-shape while_loop (§10): single runs stop when the combined sdev
    target is met, batched runs carry per-scenario stop masks, and the
    sharded batched program pmin-agrees the decision across the mesh
    (`sharding.make_stop_sync`).

`core.run` and `batch.run_batch` are thin adapters over this module.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.batch.engine import BatchResult, scenario_keys
from repro.core import integrator as core
from repro.core import map as vmap_

from . import backends as backends_mod
from . import sharding as sharding_mod
from .plan import Plan


def execute(plan: Plan, *, key=None, state: core.VegasState | None = None,
            cache=None, fill_fn=None, checkpoint_cb=None, keys=None,
            it_caps=None, edges0=None):
    """Run a plan.

    ``key`` defaults to ``PRNGKey(0)``.  ``state`` resumes a single-scenario
    run from a checkpoint; ``cache`` warm-starts a family run's importance
    maps (`batch.cache.MapCache`).  ``fill_fn`` overrides the plan's entire
    backend/sharding wiring with a custom ``fill_fn(edges, n_h, key,
    integrand)`` — the legacy `core.run` extension hook `repro.dist` built
    on; prefer expressing sharding through the plan.  ``checkpoint_cb``
    overrides the plan's checkpoint policy callback.

    Serving hooks (§12, used by `repro.serve`):

      * ``keys`` — explicit per-scenario base keys ``(B, ...)`` for a
        batched family plan, replacing the default ``fold_in(key, b)``
        derivation (`batch.engine.scenario_keys`).  A coalesced micro-batch
        keeps every request's own stream this way, so results are invariant
        to how requests were batched together.
      * ``it_caps`` — the time-budget stopping input: an iteration-count
        cap (scalar for single runs, per-scenario ``(B,)`` for batched
        runs) threaded into the while_loop carry (`core.run_loop`).
      * ``edges0`` — explicit warm-start importance maps ``(B, d, ninc+1)``
        for a batched family plan (mutually exclusive with ``cache``; the
        serving layer pools maps across batch sizes itself).

    Returns `VegasResult` (single scenario), `BatchResult` (vmapped family),
    or ``list[VegasResult]`` (``batch='serial'`` family).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if plan.grad is not None:
        # §11 route: the two-phase differentiable program (repro.grad).  It
        # is one traced program per run — none of the imperative hooks
        # (resume state, warm-start cache, fill/checkpoint overrides)
        # compose with a custom-AD boundary.
        if (state is not None or cache is not None or fill_fn is not None
                or checkpoint_cb is not None or keys is not None
                or it_caps is not None or edges0 is not None):
            raise ValueError(
                "a grad plan takes no state/cache/fill_fn/checkpoint_cb/"
                "keys/it_caps/edges0 hooks; drop the GradPolicy or the hook")
        from repro.grad.api import execute_grad
        return execute_grad(plan, key)
    if plan.is_family:
        if state is not None:
            raise ValueError("state resume is a single-scenario feature; "
                             "family runs restart from the map cache instead")
        if fill_fn is not None or checkpoint_cb is not None:
            raise ValueError(
                "fill_fn/checkpoint_cb are single-scenario hooks; express "
                "sharding and checkpointing for family runs through "
                "ExecutionConfig (mesh=..., checkpoint=...)")
        if plan.batched:
            if cache is not None and edges0 is not None:
                raise ValueError("cache and edges0 are two spellings of the "
                                 "same warm start — pass one")
            return _execute_family_vmap(plan, key, cache, keys=keys,
                                        it_caps=it_caps, edges0=edges0)
        if cache is not None or keys is not None or edges0 is not None:
            raise ValueError("cache/keys/edges0 apply to the vmapped "
                             "batch program; this plan resolved to "
                             "batch='serial'")
        return _execute_family_serial(plan, key, it_caps=it_caps)
    if cache is not None or keys is not None or edges0 is not None:
        raise ValueError("cache/keys/edges0 are family features; "
                         "single-scenario runs resume from state instead")
    return _execute_single(plan, key, state, fill_fn, checkpoint_cb,
                           it_cap=it_caps)


def _count_fills(plan: Plan, fills: int) -> None:
    """Add a run's ``fills`` (iterations, times B for a vmapped family) to
    the counters: ``fill.lanes``, the lanes the fill ran, over every shard's
    static chunk range (``n_cap`` unsharded); and on a mesh
    ``mesh.psum_bytes``, the bytes each device all-reduced."""
    obs.count("fill.lanes",
              fills * sharding_mod.fill_lanes(plan.cfg, plan.n_shards))
    if plan.n_shards > 1:
        obs.count("mesh.psum_bytes", fills * sharding_mod.psum_bytes(plan.cfg))


# --- single scenario ---------------------------------------------------------

def _plan_fill_fn(plan: Plan, *, local: bool = False):
    """The plan's fill: registry-bound, shard_mapped when the plan shards.
    ``local=True`` returns the inside-shard_map form (batched program)."""
    if plan.n_shards > 1:
        if local:
            return sharding_mod.make_local_fill(
                plan.cfg, plan.mesh, plan.shard_axes,
                backend=plan.backend.name)
        return sharding_mod.make_sharded_fill(
            plan.mesh, plan.shard_axes, plan.cfg, backend=plan.backend.name)
    return backends_mod.bind_fill(plan.cfg, backend=plan.backend.name)


#: Whole-run programs of single-scenario plans that `execute` keeps for
#: reuse, least recently used first out.
_PROGRAM_CACHE_SIZE = 32
_programs: OrderedDict[tuple, object] = OrderedDict()
_programs_lock = threading.Lock()


def _build_single_program(plan: Plan, start: int, fill_fn=None, *,
                          donate: bool):
    """The jitted ``core.run_loop`` of a single-scenario plan from iteration
    ``start``: ``prog(state[, it_cap=...]) -> state``."""
    if fill_fn is None:
        fill_fn = _plan_fill_fn(plan)
    return jax.jit(functools.partial(
        core.run_loop, integrand=plan.workload, cfg=plan.cfg, start=start,
        fill_fn=fill_fn, stop=plan.stop),
        donate_argnums=0 if donate else ())


def _cached_single_program(plan: Plan, start: int, with_cap: bool):
    """The plan's donating whole-run program, built on the first call for
    its key and reused after: jit's own cache is keyed on the function
    object, so a program built anew on every call is traced, lowered and
    fetched anew.  The key holds everything the traced program depends on,
    as objects and not ids, so a collected integrand never aliases a new
    one.  A key that does not hash (say, an integrand with list bounds)
    builds its program uncached."""
    key = (plan.workload, plan.cfg, plan.backend.name, plan.mesh,
           plan.shard_axes, plan.stop, plan.precision, start, with_cap)
    try:
        hash(key)
    except TypeError:
        return _build_single_program(plan, start, donate=True)
    with _programs_lock:
        prog = _programs.pop(key, None)
        built = prog is None
        if built:
            prog = _build_single_program(plan, start, donate=True)
        _programs[key] = prog
        if len(_programs) > _PROGRAM_CACHE_SIZE:
            _programs.popitem(last=False)
    obs.count("program.built" if built else "program.reused", 1)
    return prog


def _clear_program_cache() -> None:
    """Drop every cached program (for tests that patch traced code)."""
    with _programs_lock:
        _programs.clear()


def _execute_single(plan: Plan, key, state, fill_fn, checkpoint_cb,
                    it_cap=None):
    cfg, integrand = plan.cfg, plan.workload
    if it_cap is not None and jnp.ndim(it_cap) != 0:
        raise ValueError(
            f"a single-scenario run takes a scalar it_cap, got shape "
            f"{jnp.shape(it_cap)} (per-scenario caps are a batched-family "
            f"feature)")
    if checkpoint_cb is None and plan.checkpoint is not None:
        checkpoint_cb = plan.checkpoint.build_callback()
    if checkpoint_cb is not None and plan.stop is not None:
        # Same conflict make_plan rejects for the plan-level policy: the
        # legacy hook forces the host loop, the stop policy is the on-device
        # while_loop.  One implementation of the stop semantics, not two.
        raise ValueError(
            "checkpoint_cb forces the host loop and cannot combine with a "
            "StopPolicy (the on-device while_loop); checkpoint with a fixed "
            "loop, then resume the saved state under the stop policy")

    with obs.span("repro.init"):
        if state is None:
            state = core.init_state(integrand, cfg, key)
        # The jitted step donates its input state; work on a copy so the
        # caller's key / checkpointed state stay alive (resume safety).
        state = jax.tree.map(jnp.copy, state)
        if state.results.shape[0] < cfg.max_it:
            # Resuming under a config with more iterations: grow the buffer.
            pad = cfg.max_it - state.results.shape[0]
            filler = jnp.stack([jnp.zeros((pad,), state.results.dtype),
                                jnp.full((pad,), jnp.inf,
                                         state.results.dtype)], 1)
            state = core.VegasState(state.edges, state.n_h, state.key,
                                    state.it,
                                    jnp.concatenate([state.results, filler]))
        start = int(state.it)

    if checkpoint_cb is None:
        # On-device loop: one jitted program for the whole run (fori_loop,
        # or the stop policy's / iteration cap's fixed-shape while_loop).
        with obs.span("repro.program"):
            # A caller's fill_fn says nothing by its identity about what
            # it traces: its program is built for this call alone.
            prog = (_cached_single_program(plan, start, it_cap is not None)
                    if fill_fn is None else
                    _build_single_program(plan, start, fill_fn, donate=True))
            kw = ({} if it_cap is None
                  else {"it_cap": jnp.asarray(it_cap, jnp.int32)})
            state = prog(state, **kw)
    else:
        step = jax.jit(functools.partial(
            core.iteration_step, integrand=integrand, cfg=cfg,
            fill_fn=_plan_fill_fn(plan) if fill_fn is None else fill_fn),
            donate_argnums=0)
        end = cfg.max_it if it_cap is None else min(cfg.max_it, int(it_cap))
        for it in range(start, end):
            with obs.span("repro.program"):
                state = step(state)
            with obs.span("repro.wait"):
                jax.block_until_ready(state.results)
            checkpoint_cb(it, state)

    with obs.span("repro.wait"):
        n_it_used = int(state.it)
    _count_fills(plan, n_it_used - start)
    with obs.span("repro.finish"):
        mean, sdev, chi2_dof, n_used = core.combine_results(
            state.results, cfg.skip, n_it_used)
        means, sig2 = state.results[:, 0], state.results[:, 1]
        return core.VegasResult(float(mean), float(sdev), float(chi2_dof),
                                int(n_used), means[:n_it_used],
                                jnp.sqrt(sig2[:n_it_used]), state,
                                n_it_used=n_it_used)


# --- batched family ----------------------------------------------------------

def uniform_family_edges(family, cfg, b: int):
    """The cold-start importance maps: the family's uniform map broadcast
    over the scenario axis ``(b, d, ninc+1)``."""
    uni = vmap_.uniform_edges(family.lower, family.upper, cfg.ninc,
                              jnp.dtype(cfg.dtype))
    return jnp.broadcast_to(uni, (b,) + uni.shape)


def make_single_program(plan: Plan):
    """Build the jitted whole-run program of a single-scenario plan ONCE,
    for callers that run the same plan repeatedly — ``prog(state) ->
    state``.  Unlike the cached program inside :func:`execute` it does not
    donate its input, so one initial state can be replayed, timed in steady
    state, or lowered to read the program's HLO."""
    if plan.is_family or plan.checkpoint is not None:
        raise ValueError("make_single_program builds the single-scenario "
                         "on-device loop; use make_family_program for "
                         "batched plans")
    return _build_single_program(plan, 0, donate=False)


def make_family_program(plan: Plan, *, with_caps: bool = False):
    """Build the jitted vmapped whole-run program of a batched family plan.

    Returns ``prog(params, keys, edges0[, it_caps]) -> (states, mean, sdev,
    chi2_dof, n_used)`` with every per-scenario input carried on axis 0.
    The callable is shape-polymorphic over the batch size (jit retraces per
    B), so a long-lived caller — the serving layer's micro-batcher (§12) —
    caches ONE program per compatibility class and reuses it across bursts
    instead of paying trace+compile on every batch.  ``with_caps=True``
    threads a per-scenario iteration cap ``(B,)`` into the while_loop carry
    (the time-budget stopping input, `core.run_loop`).
    """
    family, cfg = plan.workload, plan.cfg
    fill_fn = _plan_fill_fn(plan, local=True)
    # Per-scenario stop masks come from vmapping the while_loop itself
    # (converged lanes keep their old carry); under the sharded batched
    # program the continue decision is additionally pmin-agreed across the
    # mesh so all shards run the same trip count (§10).
    stop_sync = (sharding_mod.make_stop_sync(plan.shard_axes)
                 if plan.stop is not None and plan.n_shards > 1 else None)

    def one(params, key_b, edges0_b, cap_b=None):
        ig = family.bind(params)
        st = core.init_state(ig, cfg, key_b)
        st = core.VegasState(edges0_b, st.n_h, st.key, st.it, st.results)
        st = core.run_loop(st, ig, cfg, 0, fill_fn=fill_fn, stop=plan.stop,
                           stop_sync=stop_sync, it_cap=cap_b)
        mean, sdev, chi2_dof, n_used = core.combine_results(
            st.results, cfg.skip, st.it)
        return st, mean, sdev, chi2_dof, n_used

    n_args = 4 if with_caps else 3
    batched = jax.vmap(one if with_caps
                       else lambda p, k, e: one(p, k, e))
    if plan.n_shards > 1:
        # ONE shard_map around the ENTIRE vmapped run: the per-shard fill +
        # psum runs inside the scenario vmap, every device carries the full
        # replicated O(B·KB) adaptation state, and the fill's chunk axis is
        # divided per scenario.  B integrands × D devices, one XLA program.
        batched = sharding_mod.replicated_shard_map(batched, plan.mesh,
                                                    n_args)
    return jax.jit(batched)


def package_batch_result(states, mean, sdev, chi2_dof, n_used, *,
                         warm_started: bool = False) -> BatchResult:
    """Package a family program's device outputs into a `BatchResult`.

    iter_sdevs keeps the buffer's inf sentinels past each scenario's
    n_it_used slot — consumers filter on n_it_used (combine_results
    already did, per scenario, via its n_done mask).
    """
    sig2 = np.asarray(states.results[:, :, 1])
    return BatchResult(np.asarray(mean), np.asarray(sdev),
                       np.asarray(chi2_dof), np.asarray(n_used),
                       np.asarray(states.it, dtype=np.int64),
                       np.asarray(states.results[:, :, 0]), np.sqrt(sig2),
                       states, warm_started=warm_started)


def _execute_family_vmap(plan: Plan, key, cache, *, keys=None, it_caps=None,
                         edges0=None):
    family, cfg = plan.workload, plan.cfg
    b = plan.batch_size

    if edges0 is None and cache is not None:
        edges0 = cache.get(family, cfg)
    warm = edges0 is not None
    if edges0 is None:
        edges0 = uniform_family_edges(family, cfg, b)
    edges0 = jnp.asarray(edges0)
    if edges0.shape[0] != b:
        raise ValueError(f"edges0 carries {edges0.shape[0]} scenarios, the "
                         f"plan has {b}")

    if keys is None:
        keys = scenario_keys(key, b)
    elif jnp.shape(keys)[0] != b:
        raise ValueError(f"keys carries {jnp.shape(keys)[0]} scenarios, the "
                         f"plan has {b}")

    args = [family.params, keys, edges0]
    if it_caps is not None:
        caps = jnp.asarray(it_caps, jnp.int32)
        if caps.ndim == 0:
            caps = jnp.full((b,), caps, jnp.int32)
        elif caps.shape != (b,):
            raise ValueError(f"it_caps shape {caps.shape} != ({b},)")
        args.append(caps)

    with obs.span("repro.program"):
        prog = make_family_program(plan, with_caps=it_caps is not None)
        states, mean, sdev, chi2_dof, n_used = prog(*args)
    with obs.span("repro.wait"):
        it = np.asarray(states.it, dtype=np.int64)
    # The vmapped loop runs every scenario until the last one stops.
    _count_fills(plan, int(it.max()) * b)

    with obs.span("repro.finish"):
        if cache is not None:
            cache.put(family, cfg, states.edges)
        return package_batch_result(states, mean, sdev, chi2_dof, n_used,
                                    warm_started=warm)


def _execute_family_serial(plan: Plan, key, it_caps=None):
    """The B scenarios as B independent single-scenario executions (the
    baseline the vmapped program is measured against; same per-scenario
    keys, so the streams match the batched run exactly)."""
    family = plan.workload
    out = []
    for b in range(family.batch_size):
        single = dataclasses.replace(plan, workload=family.instance(b),
                                     is_family=False, batched=False,
                                     batch_size=1)
        cap = (None if it_caps is None else
               np.broadcast_to(np.asarray(it_caps), (family.batch_size,))[b])
        out.append(_execute_single(single, jax.random.fold_in(key, b),
                                   None, None, None, it_cap=cap))
    return out
