"""Plan layer: validate one (workload, config) combination before tracing.

``make_plan`` turns a workload (`Integrand` or `IntegrandFamily`), a
`VegasConfig`, and an `ExecutionConfig` into an immutable :class:`Plan` —
the executor's sole input.  Every cross-axis constraint is checked HERE,
against the backend registry's declared capabilities, so an unsupported
combination fails with a one-line :class:`PlanError` naming the axis and the
fix — never with a tracer error from deep inside ``vmap``/``shard_map``/
Pallas lowering (DESIGN.md §9 validation rules).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

from repro import obs
from repro.batch.family import IntegrandFamily
from repro.core import integrator as core

from . import backends as backends_mod
from . import sharding as sharding_mod
from . import config as config_mod
from .config import (BATCH_MODES, GRAD_MODES, CheckpointPolicy,
                     ExecutionConfig, GradPolicy, PrecisionPolicy, StopPolicy)


class PlanError(ValueError):
    """An invalid execution-plan combination, rejected at plan time."""


@dataclasses.dataclass(frozen=True)
class Plan:
    """A validated, fully-resolved execution plan (what `execute` runs)."""
    workload: Any                       # Integrand | IntegrandFamily
    cfg: core.ResolvedConfig            # algorithm parameters, resolved
    execution: ExecutionConfig
    backend: backends_mod.BackendSpec
    is_family: bool                     # workload has a scenario axis
    batched: bool                       # True => vmapped family program
    batch_size: int                     # scenarios (1 for a single Integrand)
    mesh: Any                           # None when unsharded
    shard_axes: tuple[str, ...]
    n_shards: int
    checkpoint: CheckpointPolicy | None
    stop: StopPolicy | None             # None, or an ACTIVE policy (§10)
    grad: GradPolicy | None = None      # None, or an ACTIVE policy (§11)
    tuned: Any = None                   # TuneReport when the knobs came from
                                        # the measured cost model (§13)
    precision: PrecisionPolicy | None = None  # RESOLVED (sample, accum)
                                        # pair, both names concrete (§15)

    def describe(self) -> str:
        w = self.workload
        lines = [
            f"plan: {getattr(w, 'name', type(w).__name__)} "
            f"(dim={self.cfg.dim}, neval={self.cfg.neval}, "
            f"max_it={self.cfg.max_it})",
            f"  backend    {self.backend.name} "
            f"[{', '.join(sorted(self.backend.capabilities))}]",
            f"  batching   {'vmap B=' + str(self.batch_size) if self.batched else ('serial B=' + str(self.batch_size) if self.batch_size > 1 else 'single scenario')}",
            f"  sharding   {str(self.n_shards) + ' shards @ ' + ','.join(self.shard_axes) if self.n_shards > 1 else 'none'}",
            f"  loop       {'host (checkpointing)' if self.checkpoint else ('on-device while_loop [stop: ' + self.stop.describe() + ']' if self.stop else 'on-device fori_loop')}",
            f"  grad       {self.grad.describe() + ' (two-phase: stop_gradient adapt -> frozen-map eval, §11)' if self.grad else 'off'}",
        ]
        if self.precision is not None:
            p = self.precision
            note = ("" if p.accum_dtype == p.sample_dtype else
                    " (products stay in the sample dtype; running sums "
                    "widened, §15)")
            lines.append(f"  precision  {p.describe()}{note}")
        if self.tuned is not None:
            lines.append(f"  knobs      {self.tuned.describe()}")
        return "\n".join(lines)


def make_plan(workload, cfg: core.VegasConfig | None = None,
              execution: ExecutionConfig | None = None) -> Plan:
    """Resolve + validate one run.  ``execution=None`` takes the config's own
    ``cfg.execution``; passing both lets callers keep one algorithm config
    and vary the execution axes (the sweep CLI does this)."""
    with obs.span("repro.plan"):
        return _make_plan(workload, cfg, execution)


def _make_plan(workload, cfg, execution) -> Plan:
    cfg = cfg or core.VegasConfig()
    if execution is None:
        execution = cfg.execution
    elif execution is not cfg.execution:
        cfg = cfg.with_execution(execution)
    if execution.backend == "auto":
        # Resolve the platform default (pallas-fused on TPU, ref elsewhere)
        # BEFORE the autotuner and the capability checks, so both see the
        # concrete backend and the Plan records it.
        execution = dataclasses.replace(
            execution, backend=backends_mod.backend_default())
        cfg = cfg.with_execution(execution)
    tuned = None
    if execution.autotune:
        # §13: the cost-model chooser replaces cfg's chunk/tile/batch/shard
        # knobs with the predicted-fastest VALID combination (candidates are
        # probed through make_plan itself with autotune=False, so the tuner
        # cannot emit a plan this function would reject — and its fallback
        # is the caller's own knobs, so autotuning never loses a plan that
        # explicit knobs would have admitted).
        from . import autotune as autotune_mod
        cfg, tuned = autotune_mod.tune(workload, cfg)
        execution = cfg.execution
    rcfg = cfg.resolve(workload.dim)

    # --- backend axis -------------------------------------------------------
    try:
        spec = backends_mod.get(execution.backend)
    except KeyError as e:
        raise PlanError(str(e)) from None
    # Normalize any jnp.dtype()-accepted spelling before comparing against
    # the spec's declared names ('f4', np.float64, jnp.float32, ... all ok).
    dtype_name = jnp.dtype(rcfg.dtype).name
    if dtype_name not in spec.dtypes:
        raise PlanError(
            f"backend {spec.name!r} supports dtypes {spec.dtypes}, got "
            f"dtype={dtype_name!r}"
            + (" (the in-kernel RNG reproduces the f32 uniform bit pattern)"
               if spec.supports(backends_mod.IN_KERNEL_RNG) else ""))

    # --- precision axis (§15) ----------------------------------------------
    prec = execution.precision
    if prec is not None and prec.sample_dtype is not None:
        sample_name = jnp.dtype(prec.sample_dtype).name
        if sample_name != dtype_name:
            raise PlanError(
                f"PrecisionPolicy(sample_dtype={sample_name!r}) conflicts "
                f"with cfg.dtype={dtype_name!r} — the sample dtype has one "
                f"source of truth; leave sample_dtype=None to inherit it")
    accum_name = (jnp.dtype(prec.accum_dtype).name
                  if prec is not None and prec.accum_dtype is not None
                  else dtype_name)
    if (dtype_name, accum_name) not in spec.precisions:
        pairs = ", ".join(f"{s}->{a}" for s, a in spec.precisions)
        raise PlanError(
            f"backend {spec.name!r} supports precision pairs [{pairs}], got "
            f"{dtype_name}->{accum_name}")
    if accum_name != dtype_name and "interpret" in spec.knobs:
        from repro import kernels
        if not kernels.resolve_interpret(execution.interpret):
            raise PlanError(
                f"backend {spec.name!r} compiled for TPU cannot accumulate "
                f"in {accum_name}: Mosaic lowers no float64 (drop the "
                f"widened PrecisionPolicy, or run interpret=True)")
    import jax.dtypes as _jdtypes
    if accum_name != dtype_name and \
            _jdtypes.canonicalize_dtype(accum_name).name != accum_name:
        # jnp silently narrows f64 arrays when x64 is off — a widened
        # accumulator would silently degrade to the plain-f32 run.
        raise PlanError(
            f"accum_dtype={accum_name!r} needs x64 enabled: set "
            f"JAX_ENABLE_X64=1 / call repro.launch.env.enable_x64(True) "
            f"before building programs")
    precision = config_mod.PrecisionPolicy(sample_dtype=dtype_name,
                                           accum_dtype=accum_name)
    # The knob universe comes from the registry itself, so a knob added to
    # one BackendSpec is automatically validated against every other.
    all_knobs = set().union(*(backends_mod.get(n).knobs
                              for n in backends_mod.available()))
    for knob in sorted(all_knobs):
        if (getattr(execution, knob, None) is not None
                and knob not in spec.knobs):
            raise PlanError(
                f"{knob}={getattr(execution, knob)!r} is not a knob of "
                f"backend {spec.name!r} (accepted: {spec.knobs or 'none'})")

    # --- batch axis ---------------------------------------------------------
    is_family = isinstance(workload, IntegrandFamily) or (
        hasattr(workload, "params") and hasattr(workload, "bind"))
    if execution.batch not in BATCH_MODES:
        raise PlanError(f"batch={execution.batch!r} is not one of {BATCH_MODES}")
    if not is_family:
        if execution.batch == "vmap":
            raise PlanError(
                f"batch='vmap' needs an IntegrandFamily workload with a "
                f"scenario axis; got a plain integrand "
                f"{getattr(workload, 'name', workload)!r}")
        batched, batch_size = False, 1
    else:
        batch_size = workload.batch_size
        if execution.batch == "serial":
            batched = False
        else:
            if not spec.supports(backends_mod.VMAPPABLE):
                if execution.batch == "vmap":
                    raise PlanError(
                        f"backend {spec.name!r} does not declare "
                        f"'{backends_mod.VMAPPABLE}'; use batch='serial' or a "
                        f"vmappable backend ({_caps(backends_mod.VMAPPABLE)})")
                batched = False   # auto: fall back to the serial loop
            else:
                batched = True

    # --- sharding axis ------------------------------------------------------
    mesh, shard_axes, n_shards = execution.mesh, execution.shard_axes, 1
    if shard_axes and mesh is None:
        raise PlanError(f"shard_axes={shard_axes} given without a mesh")
    if mesh is not None:
        shard_axes = tuple(shard_axes or mesh.axis_names)
        missing = [a for a in shard_axes if a not in mesh.axis_names]
        if missing:
            raise PlanError(
                f"shard axes {missing} not in mesh axes "
                f"{tuple(mesh.axis_names)}")
        n_shards = sharding_mod.mesh_shard_count(mesh, shard_axes)
        if n_shards > 1 and not spec.supports(backends_mod.SHARDABLE):
            raise PlanError(
                f"backend {spec.name!r} does not declare "
                f"'{backends_mod.SHARDABLE}'; shardable backends: "
                f"{_caps(backends_mod.SHARDABLE)}")
        if n_shards > rcfg.n_cap // rcfg.chunk:
            # Merely-uneven divisions are fine (trailing shards accumulate
            # masked zeros, DESIGN.md C2); rejected is only the degenerate
            # case where shards outnumber chunks, i.e. devices cannot own
            # work even at one chunk apiece.
            raise PlanError(
                f"{n_shards} shards but only {rcfg.n_cap // rcfg.chunk} "
                f"chunks: more devices than units of work — lower the "
                f"device count or the chunk size ({rcfg.chunk})")
    else:
        shard_axes = ()

    # --- checkpoint axis ----------------------------------------------------
    ckpt = execution.checkpoint
    if ckpt is not None:
        if is_family:
            raise PlanError(
                "checkpointing is a single-scenario, host-loop policy; a "
                "family run restarts from the warm-start map cache "
                "(batch.cache.MapCache) instead")
        if ckpt.directory is None and ckpt.callback is None:
            raise PlanError(
                "CheckpointPolicy needs a directory or a callback")

    # --- stop axis ----------------------------------------------------------
    stop = execution.stop
    if stop is not None:
        if stop.rtol < 0 or stop.atol < 0 or stop.min_it < 0:
            raise PlanError(
                f"StopPolicy fields must be non-negative, got "
                f"rtol={stop.rtol}, atol={stop.atol}, min_it={stop.min_it}")
        if not stop.active:
            stop = None  # rtol == atol == 0: inert, run the fixed loop
    if stop is not None:
        if ckpt is not None:
            raise PlanError(
                "stop + checkpoint conflict: a StopPolicy runs the "
                "on-device while_loop, a CheckpointPolicy forces the "
                "per-iteration host loop — drop one (resuming FROM a "
                "checkpoint into a stop-policy run is supported: pass the "
                "restored state to run/execute)")
        if not spec.supports(backends_mod.EARLY_STOP):
            raise PlanError(
                f"backend {spec.name!r} does not declare "
                f"'{backends_mod.EARLY_STOP}'; early-stop capable backends: "
                f"{_caps(backends_mod.EARLY_STOP)}")
        if stop.min_it >= rcfg.max_it:
            raise PlanError(
                f"StopPolicy(min_it={stop.min_it}) >= max_it="
                f"{rcfg.max_it}: the policy could never stop early — "
                f"lower min_it or drop the policy")

    # --- grad axis ----------------------------------------------------------
    grad = execution.grad
    if grad is not None:
        if grad.mode not in GRAD_MODES:
            raise PlanError(
                f"GradPolicy.mode={grad.mode!r} is not one of {GRAD_MODES}")
        if not grad.active:
            grad = None  # mode='off': inert, plain run
    if grad is not None:
        cap = (backends_mod.GRAD_PATHWISE if grad.mode == "pathwise"
               else backends_mod.GRAD_SCORE)
        if not spec.supports(cap):
            hint = (" (the fused kernel regenerates its RNG in-kernel — "
                    "there is no JAX-level sample path to differentiate; "
                    "use 'ref' or 'pallas')"
                    if spec.supports(backends_mod.IN_KERNEL_RNG) else "")
            raise PlanError(
                f"backend {spec.name!r} does not declare '{cap}'; "
                f"grad-capable backends for mode={grad.mode!r}: "
                f"{_caps(cap)}{hint}")
        if ckpt is not None:
            raise PlanError(
                "grad + checkpoint conflict: the two-phase differentiable "
                "run is one traced program, a CheckpointPolicy forces the "
                "per-iteration host loop — drop one")
        if n_shards > 1:
            raise PlanError(
                "grad + mesh is not supported yet: the differentiable eval "
                "pass is not wired through shard_map — drop the mesh (the "
                "adapt phase alone does not dominate grad runs)")
        if accum_name != dtype_name:
            raise PlanError(
                "grad + widened accumulation is not supported yet: the "
                "two-phase custom VJP/JVP primal types are the sample "
                "dtype — drop the PrecisionPolicy or the GradPolicy")

    return Plan(workload=workload, cfg=rcfg, execution=execution,
                backend=spec, is_family=is_family, batched=batched,
                batch_size=batch_size, mesh=mesh, shard_axes=shard_axes,
                n_shards=n_shards, checkpoint=ckpt, stop=stop, grad=grad,
                tuned=tuned, precision=precision)


def _caps(capability: str) -> list[str]:
    return [n for n in backends_mod.available()
            if backends_mod.get(n).supports(capability)]
