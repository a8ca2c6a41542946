"""Execution configuration: HOW a VEGAS+ run executes, split from WHAT it
computes.

`core.integrator.VegasConfig` carries the algorithm parameters (neval, ninc,
alpha, beta, ... — the paper's Table 2 names); :class:`ExecutionConfig`
carries the orthogonal execution axes the engine composes
(DESIGN.md §9, §10):

  * **backend**  — which fill implementation (`engine.backends` registry:
                   ``ref`` / ``pallas`` / ``pallas-fused``, or ``auto``
                   for the platform default) plus its knobs
                   (``interpret``, ``tile``);
  * **batching** — how an `IntegrandFamily` workload executes (``vmap`` over
                   the scenario axis vs a ``serial`` per-scenario loop);
  * **sharding** — a device mesh + axis names to shard the fill's global
                   chunk axis over (`engine.sharding`);
  * **checkpointing** — a :class:`CheckpointPolicy` that switches the run to
                   the host-side loop and persists `VegasState` every
                   iteration (`dist.checkpoint`);
  * **stopping**  — a :class:`StopPolicy` convergence target (rtol/atol/
                   min_it) that turns the fixed ``fori_loop`` into an
                   adaptive fixed-shape ``lax.while_loop`` (DESIGN.md §10);
  * **autotuning** — ``autotune=True`` asks ``make_plan`` to pick the
                   chunk/tile/batch-split/shard knobs from the measured
                   per-device cost model (`engine.autotune`, DESIGN.md §13;
                   ``cost_table`` overrides the table lookup);
  * **gradients** — a :class:`GradPolicy` that makes the run differentiable
                   (`repro.grad`, DESIGN.md §11): adapt with gradients
                   stopped, then a frozen-map evaluation pass whose pathwise
                   (or score-function) Monte Carlo gradient flows to
                   integrand parameters and integration bounds.

The split exists so that every run path — single scenario, batched family,
sharded fill, and their combinations — consumes ONE config object instead of
re-threading backend flags by hand (the config sprawl this replaces).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

#: Legacy flat `VegasConfig` fields that now live on ExecutionConfig.
LEGACY_EXEC_FIELDS = ("backend", "interpret", "fused_cubes", "tile")

#: Valid values of ExecutionConfig.batch.
BATCH_MODES = ("auto", "vmap", "serial")

#: Valid values of GradPolicy.mode ("off" normalizes to no policy at plan
#: time, mirroring the inert-StopPolicy convention).
GRAD_MODES = ("pathwise", "score", "off")


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Per-plan numeric precision policy (DESIGN.md §15).

    ``sample_dtype`` is the dtype samples, transforms, and integrand products
    are computed in; ``accum_dtype`` is the dtype the fill's moment
    accumulators (map histogram, per-cube s1/s2) carry.  Either may be None:
    ``sample_dtype=None`` inherits the algorithm config's own ``dtype`` and
    ``accum_dtype=None`` matches the sample dtype (the classic single-dtype
    run).  The interesting split is ``f32 -> f64``: products stay f32 — on
    the fused TPU kernel they must, the MXU sums in f32 and the in-kernel
    RNG reproduces the f32 uniform bit pattern — but every running sum is
    widened to f64 before accumulation, cuVegas' own double-precision
    accumulator design.

    ``make_plan`` validates the resolved ``(sample, accum)`` pair against
    the backend registry's declared capability pairs
    (`BackendSpec.precisions`) and rejects unsupported combinations with a
    one-line PlanError — e.g. ``f64`` samples on a fused backend (the RNG
    contract is f32-only) or a widened accumulator without x64 enabled.
    """
    sample_dtype: str | None = None   # None = inherit VegasConfig.dtype
    accum_dtype: str | None = None    # None = same as sample_dtype

    @property
    def widened(self) -> bool:
        """True when accumulation runs wider than sampling (the policy does
        something beyond the classic single-dtype run)."""
        if self.accum_dtype is None:
            return False
        import numpy as np
        return (np.dtype(self.accum_dtype).itemsize
                > np.dtype(self.sample_dtype or "float32").itemsize)

    def describe(self) -> str:
        s = self.sample_dtype or "cfg"
        a = self.accum_dtype or s
        return f"{s}->{a}"


@dataclasses.dataclass(frozen=True)
class GradPolicy:
    """Differentiable-integration policy (DESIGN.md §11, `repro.grad`).

    A run under an active policy executes in two phases: the adaptive loop
    runs with every gradient stopped (map and stratification evolution are
    ``stop_gradient``-frozen), then ONE frozen-map evaluation pass produces
    the returned estimate.  For a fixed map the estimator is unbiased
    whatever the map, so dropping the adaptation's parameter-dependence is
    unbiased for the frozen-map estimate — and the eval pass's gradient is
    an exact Monte Carlo estimator of ``dI/dtheta``.

    ``mode`` selects the estimator the backward pass evaluates:

      * ``pathwise`` — the reparameterized gradient ``E[J(y) df/dtheta]``:
        samples are a fixed function of (frozen map, chunk-keyed uniforms),
        so differentiating the integrand along each sample path is exact.
      * ``score``    — the log-derivative form ``E[J f d(log f)/dtheta]``:
        equal to pathwise wherever ``f > 0`` (``f dlog f = df``) but needing
        only the score of the integrand — the form available when ``f`` is
        computed in log-space (Bayesian-evidence workloads); samples with
        ``f <= 0`` contribute zero gradient.
      * ``off``      — inert; `make_plan` normalizes the policy to ``None``.

    ``with_sdev`` asks terminal runners (the executor / CLIs) to also
    estimate each gradient component's own Monte Carlo uncertainty by
    integrating the derivative integrand through the same frozen-map pass
    (one extra fill per parameter component).
    """
    mode: str = "pathwise"
    with_sdev: bool = True

    @property
    def active(self) -> bool:
        return self.mode != "off"

    def describe(self) -> str:
        bits = [self.mode]
        if self.with_sdev:
            bits.append("with_sdev")
        return ",".join(bits)


@dataclasses.dataclass(frozen=True)
class StopPolicy:
    """Convergence target for the adaptive iteration loop (DESIGN.md §10).

    A run stops once its inverse-variance combined estimate satisfies the
    vegas package's criterion ``sdev <= max(rtol * |mean|, atol)`` AND at
    least ``min_it`` iterations have executed.  With both tolerances at 0
    the policy is inert (``make_plan`` normalizes it to ``None`` and the
    fixed-length ``fori_loop`` runs).

    The loop stays a fixed-shape ``lax.while_loop`` — the results buffer is
    always ``(max_it, 2)`` and unfilled slots keep the ``sigma2 = inf``
    sentinel — so a stop-policy program is jittable, vmappable (per-scenario
    stop masks come from the while_loop batching rule), and resumes from
    the same checkpoints as a fixed run.  ``skip`` iterations never enter
    the combination, so the loop cannot stop before ``skip + 1`` iterations
    regardless of ``min_it`` (the combined sdev is still ``inf`` there).
    """
    rtol: float = 0.0
    atol: float = 0.0
    min_it: int = 2

    @property
    def active(self) -> bool:
        return self.rtol > 0.0 or self.atol > 0.0

    def converged(self, mean, sdev, n_done):
        """Traced convergence predicate on the running combined stats."""
        import jax.numpy as jnp
        target = jnp.maximum(self.rtol * jnp.abs(mean), self.atol)
        return (n_done >= self.min_it) & (sdev <= target)

    def describe(self) -> str:
        bits = []
        if self.rtol > 0:
            bits.append(f"rtol={self.rtol:g}")
        if self.atol > 0:
            bits.append(f"atol={self.atol:g}")
        bits.append(f"min_it={self.min_it}")
        return ",".join(bits)


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """When and where to persist `VegasState` during a run.

    Any policy forces the host-side iteration loop (checkpointing is
    inherently a host sync, DESIGN.md §5.3).  Either give a ``directory``
    (a `dist.checkpoint.CheckpointManager` is built with ``keep`` retention)
    or a ``callback(it, state)`` of your own; ``every`` throttles how often
    the save fires (the host loop still runs every iteration).
    """
    directory: str | None = None
    keep: int = 3
    every: int = 1
    callback: Callable[[int, Any], None] | None = None

    def build_callback(self) -> Callable[[int, Any], None]:
        base = self.callback
        if base is None:
            if self.directory is None:
                raise ValueError(
                    "CheckpointPolicy needs a directory or a callback")
            from repro.dist.checkpoint import CheckpointManager
            mgr = CheckpointManager(self.directory, keep=self.keep)
            base = lambda it, state: mgr.save(it, state)
        if self.every <= 1:
            return base
        every = self.every

        def throttled(it, state):
            if (it + 1) % every == 0:
                base(it, state)
        return throttled


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """The execution axes, as data.  Validation happens at plan time
    (`engine.plan.make_plan`), not here — so configs stay cheap to build and
    the error surfaces exactly once, with the full workload context."""
    backend: str = "ref"            # engine.backends registry name, or
                                    # 'auto' = platform default
                                    # (backends.backend_default:
                                    # pallas-fused on TPU, ref elsewhere)
    interpret: bool | None = None   # pallas mode; None = platform autodetect
    tile: int | None = None         # pallas tile; None = VMEM autotune
    batch: str = "auto"             # family execution: auto | vmap | serial
    mesh: Any = None                # jax Mesh; None = unsharded
    shard_axes: tuple[str, ...] | None = None  # mesh axes to shard fill over
    checkpoint: CheckpointPolicy | None = None
    stop: StopPolicy | None = None  # convergence target -> while_loop (§10)
    grad: GradPolicy | None = None  # differentiable two-phase run (§11)
    precision: PrecisionPolicy | None = None  # sample/accum dtype pair (§15):
                                    # None = single-dtype run in cfg.dtype;
                                    # PrecisionPolicy(accum_dtype='float64')
                                    # widens the moment accumulators
    autotune: bool = False          # measured-cost-model knob choice (§13):
                                    # make_plan picks chunk/tile/batch/shard
                                    # via engine.autotune.tune
    cost_table: Any = None          # autotune table override: a CostTable or
                                    # a path; None = resolve_table order

    def with_legacy(self, **flat) -> "ExecutionConfig":
        """Fold the pre-engine flat `VegasConfig` fields (``backend``,
        ``interpret``, ``fused_cubes``, ``tile``) into this config — the
        deprecation shim `VegasConfig.__init__` applies.

        Legacy ``backend='pallas'`` meant the *fused* kernel unless
        ``fused_cubes=False`` was also passed; the registry names the two
        paths explicitly (``pallas-fused`` vs ``pallas``).
        """
        unknown = set(flat) - set(LEGACY_EXEC_FIELDS)
        if unknown:
            raise TypeError(f"unknown execution fields: {sorted(unknown)}")
        backend = flat.get("backend", self.backend)
        # The remap applies only when a legacy backend/fused_cubes kwarg was
        # actually given — an explicitly chosen registry name (e.g.
        # ExecutionConfig(backend='pallas') for P-V2) must never be upgraded
        # just because some other legacy kwarg (interpret/tile) rode along.
        if "backend" in flat or "fused_cubes" in flat:
            default_fused = ("backend" in flat
                             or self.backend == "pallas-fused")
            fused = flat.get("fused_cubes", default_fused)
            if backend in ("pallas", "pallas-fused"):
                backend = "pallas-fused" if fused else "pallas"
        kw = {k: flat[k] for k in ("interpret", "tile") if k in flat}
        return dataclasses.replace(self, backend=backend, **kw)

    def describe(self) -> str:
        bits = [f"backend={self.backend}"]
        if self.interpret is not None:
            bits.append(f"interpret={self.interpret}")
        if self.tile is not None:
            bits.append(f"tile={self.tile}")
        if self.batch != "auto":
            bits.append(f"batch={self.batch}")
        if self.mesh is not None:
            axes = self.shard_axes or tuple(self.mesh.axis_names)
            shape = "x".join(str(self.mesh.shape[a]) for a in axes)
            bits.append(f"shard={shape}@{','.join(axes)}")
        if self.checkpoint is not None:
            bits.append("checkpoint=on")
        if self.stop is not None and self.stop.active:
            bits.append(f"stop[{self.stop.describe()}]")
        if self.grad is not None and self.grad.active:
            bits.append(f"grad[{self.grad.describe()}]")
        if self.precision is not None:
            bits.append(f"precision[{self.precision.describe()}]")
        if self.autotune:
            bits.append("autotune")
        return " ".join(bits)
