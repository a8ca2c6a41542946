"""Capability-declaring fill-backend registry.

Every fill implementation registers a :class:`BackendSpec` here: the callable
(one shared contract, ``fill(edges, n_h, key, integrand, *, nstrat, n_cap,
chunk, dtype, start_chunk, n_chunks, kahan, **knobs) -> FillResult``), the
**capabilities** it declares, the ExecutionConfig **knobs** it accepts, and
the accumulation dtypes it supports.  Plan validation
(`engine.plan.make_plan`) reads the declarations and rejects unsupported
backend × axis combinations loudly at plan time — instead of the historical
failure mode, an opaque tracer error from deep inside `shard_map`/`vmap`.

Capabilities (DESIGN.md §9 capability matrix):

  * ``shardable``        — honors ``start_chunk``/``n_chunks`` + ``kahan``
                           under ``shard_map`` (the C5 chunk contract);
  * ``vmappable``        — traces correctly under ``jax.vmap`` over an
                           `IntegrandFamily`'s parameter axis;
  * ``in-kernel-rng``    — regenerates its uniforms inside the kernel
                           (no per-eval RNG traffic when compiled, P-V3);
  * ``closure-hoisting`` — accepts integrands that close over arrays
                           (ridge's peak table, vmapped family params);
  * ``early-stop``       — traces correctly inside the adaptive
                           ``lax.while_loop`` body (`StopPolicy` runs, §10):
                           no iteration-index specialization, no host
                           callbacks inside the fill;
  * ``grad-pathwise``    — can anchor the differentiable two-phase run
                           (`GradPolicy(mode='pathwise')`, §11): the eval
                           pass's value may come from this backend while the
                           cotangent is evaluated through the reference
                           formulation on the SAME chunk-keyed stream (the
                           bit-exact RNG contract is what makes the pairing
                           coherent).  ``pallas-fused`` cannot declare it:
                           with the RNG regenerated inside the kernel and
                           moments accumulated in VMEM there is no JAX-level
                           sample path left to pair a VJP against;
  * ``grad-score``       — supports the score-function gradient fallback
                           (`GradPolicy(mode='score')`): the surrogate
                           rewrites the integrand sample-by-sample, which
                           needs the reference (pure-jnp) eval path.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Mapping

from repro.core import fill as fill_mod

log = logging.getLogger("repro.engine")

SHARDABLE = "shardable"
VMAPPABLE = "vmappable"
IN_KERNEL_RNG = "in-kernel-rng"
CLOSURE_HOISTING = "closure-hoisting"
EARLY_STOP = "early-stop"
GRAD_PATHWISE = "grad-pathwise"
GRAD_SCORE = "grad-score"

CAPABILITIES = (SHARDABLE, VMAPPABLE, IN_KERNEL_RNG, CLOSURE_HOISTING,
                EARLY_STOP, GRAD_PATHWISE, GRAD_SCORE)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered fill implementation + its declared envelope."""
    name: str
    fill: Callable[..., Any]
    capabilities: frozenset
    knobs: tuple[str, ...] = ()       # ExecutionConfig fields forwarded as kwargs
    fixed: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    #: Declared (sample_dtype, accum_dtype) capability pairs (DESIGN.md §15):
    #: which PrecisionPolicy combinations this fill implements.  Samples on
    #: the kernel backends are pinned to f32 by the in-kernel RNG contract
    #: (the threefry mantissa trick reproduces the f32 uniform bit pattern);
    #: widened f32->f64 accumulation is a separate, declarable capability.
    precisions: tuple[tuple[str, str], ...] = (("float32", "float32"),)
    doc: str = ""

    def supports(self, capability: str) -> bool:
        return capability in self.capabilities

    @property
    def dtypes(self) -> tuple[str, ...]:
        """Accepted SAMPLE dtypes, derived from the precision pairs (the
        pre-§15 single-axis declaration, kept for messages and callers)."""
        return tuple(dict.fromkeys(s for s, _ in self.precisions))


_REGISTRY: dict[str, BackendSpec] = {}


def register(spec: BackendSpec, *, overwrite: bool = False) -> BackendSpec:
    if spec.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {spec.name!r} already registered")
    bad = set(spec.capabilities) - set(CAPABILITIES)
    if bad:
        raise ValueError(f"unknown capabilities {sorted(bad)}; "
                         f"known: {CAPABILITIES}")
    _REGISTRY[spec.name] = spec
    return spec


def available() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown fill backend {name!r}; registered: {available()}"
        ) from None


def bind_fill(rcfg, *, backend: str | None = None, **overrides) -> Callable:
    """Bind a registered backend to a resolved config.

    Returns ``fill(edges, n_h, key, integrand, **runtime)`` with the
    geometry (``nstrat``/``n_cap``/``chunk``/``dtype``), the spec's pinned
    kwargs, and the backend's declared ExecutionConfig knobs already applied.
    ``overrides`` (e.g. ``kahan=True`` for sharded partials) win last.
    This is the single replacement for the old ``fill_mod.BACKENDS`` dict +
    the per-call-site kwargs threading.
    """
    import jax.numpy as jnp

    spec = get(backend if backend is not None else rcfg.execution.backend)
    kw = dict(nstrat=rcfg.nstrat, n_cap=rcfg.n_cap, chunk=rcfg.chunk,
              dtype=jnp.dtype(rcfg.dtype))
    prec = getattr(rcfg.execution, "precision", None)
    if prec is not None and prec.accum_dtype is not None:
        # The §15 accumulation dtype rides the same binding point as the
        # sample dtype, so every fill call site (executor, sharding, serve,
        # autotune probes) inherits the policy without new threading.
        kw["accum_dtype"] = jnp.dtype(prec.accum_dtype)
    kw.update(spec.fixed)
    for knob in spec.knobs:
        kw[knob] = getattr(rcfg.execution, knob)
    kw.update(overrides)
    return functools.partial(spec.fill, **kw)


def capability_matrix() -> str:
    """Human-readable capability table (the `--plan` CLI output and
    DESIGN.md §9 render this)."""
    lines = ["backend          " + "  ".join(f"{c:<16}" for c in CAPABILITIES)]
    for name in available():
        spec = _REGISTRY[name]
        row = "  ".join(f"{'yes' if spec.supports(c) else '-':<16}"
                       for c in CAPABILITIES)
        lines.append(f"{name:<17}{row}")
    return "\n".join(lines)


# --- the built-in backends ---------------------------------------------------

register(BackendSpec(
    name="ref",
    fill=fill_mod.fill_reference,
    capabilities=frozenset({SHARDABLE, VMAPPABLE, CLOSURE_HOISTING,
                            EARLY_STOP, GRAD_PATHWISE, GRAD_SCORE}),
    knobs=(),
    precisions=(("float32", "float32"), ("float32", "float64"),
                ("float64", "float64"), ("float64", "float32")),
    doc="pure-jnp oracle: scatter-add accumulation, chunked lax.scan",
))

register(BackendSpec(
    name="pallas",
    fill=fill_mod.fill_pallas,
    capabilities=frozenset({SHARDABLE, VMAPPABLE, CLOSURE_HOISTING,
                            EARLY_STOP, GRAD_PATHWISE}),
    knobs=("interpret", "tile"),
    fixed={"fused_cubes": False},
    precisions=(("float32", "float32"), ("float32", "float64")),
    doc="P-V2 baseline kernel: uniforms in / weights out, XLA segment-sum",
))

register(BackendSpec(
    name="pallas-fused",
    fill=fill_mod.fill_pallas,
    capabilities=frozenset({SHARDABLE, VMAPPABLE, IN_KERNEL_RNG,
                            CLOSURE_HOISTING, EARLY_STOP}),
    knobs=("interpret", "tile"),
    fixed={"fused_cubes": True},
    precisions=(("float32", "float32"), ("float32", "float64")),
    doc="P-V3 streaming kernel: in-kernel RNG + in-kernel cube moments",
))


# --- the platform default ----------------------------------------------------

#: The registry backend each platform compiles natively; every other
#: platform resolves to the ``ref`` oracle.
PLATFORM_BACKENDS = {"tpu": "pallas-fused"}


@functools.lru_cache(maxsize=None)
def _announce_default(platform: str, kind: str, name: str) -> None:
    log.info("Platform-default fill backend: %s (platform=%s, "
             "device_kind=%s)", name, platform, kind)


def backend_default() -> str:
    """The registry backend this platform compiles natively:
    ``'pallas-fused'`` on TPU, ``'ref'`` elsewhere (CPU CI, where the
    pallas backends still run, interpreted, when asked for explicitly).
    Logs the detected ``device_kind`` once per process;
    ``ExecutionConfig(backend='auto')`` resolves through this."""
    import jax

    from repro import kernels
    platform = jax.default_backend()
    name = PLATFORM_BACKENDS.get(platform, "ref")
    _announce_default(platform, kernels.device_kind(), name)
    return name
