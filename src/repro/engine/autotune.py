"""Plan autotuner: a measured cost model that picks execution knobs per plan
(DESIGN.md §13).

The engine exposes five execution knobs — ``chunk``, ``tile``, backend
execution mode, batch split, shard axes — that were each defaulted
independently (``chunk`` is whatever the caller passed, ``tile`` comes from a
static VMEM model, ...).  cuVegas' central performance claim rests on fitting
the workload distribution to the hardware instead of fixed heuristics; this
module is that piece for our engine, and it is what makes later hardware
ports self-tuning instead of re-defaulted.

Three layers:

  * **calibration** (:func:`calibrate`): time the jitted fill hot path over
    a small grid of (backend, d, neval, chunk, tile) shapes — steady-state,
    compile excluded — and fit per-class :class:`ClassCoeffs` by
    non-negative least squares.  The fitted :class:`CostTable` is keyed by
    (device kind, jax backend, git sha) and persists as JSON
    (:meth:`CostTable.save`);
  * **prediction** (:meth:`ClassCoeffs.fill_s` / :func:`predict_run_s`):
    given a plan's geometry (d, ninc, n_cubes, neval, B, mesh), predict wall
    time for any candidate knob combination.  All coefficients are
    non-negative, so the prediction is monotone in the work terms
    (property-tested: monotone in ``neval``);
  * **choice** (:func:`tune`, invoked by ``make_plan(...,
    ExecutionConfig(autotune=True))``): enumerate candidate knob
    combinations, sort by predicted cost, and PROBE each through
    ``make_plan`` itself until one validates.  Validity is never re-derived
    here — it is delegated to the registry capability/knob declarations and
    the kernel's ``ops.valid_tiles`` divisor/VMEM rules — so the tuner
    cannot emit a plan ``make_plan`` would reject, and its final fallback is
    the caller's own knobs (autotuning never loses a plan that explicit
    knobs would have admitted).

The serving layer shares the same tables: :class:`OnlineCost` keeps the
service's min-observed per-scenario-iteration cost semantics exactly and
uses a `CostTable` only as the PRIOR for classes that have not executed yet
(so a request's first batch can already be budget-enforced).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
from typing import Any, Mapping

import numpy as np

#: Default on-disk table name (``CostTable.save`` of a :func:`calibrate`
#: run, read back by ``resolve_table``).
DEFAULT_TABLE_PATH = "COST_TABLE.json"

#: Environment variable naming a table file, so every ``--autotune`` run
#: of a shell shares one calibration.
TABLE_ENV = "REPRO_COST_TABLE"

#: Candidate chunk sizes the tuner enumerates (powers of two; the caller's
#: own chunk is always added so the tuner can only deviate when the model
#: predicts a strict win).
CHUNK_CANDIDATES = tuple(1 << p for p in range(9, 18))  # 512 .. 131072


def _git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short=12", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def class_key(backend: str, interpret: bool | None = None) -> str:
    """The cost-model class of a (backend, execution-mode) pair, qualified
    by the device that produced the timings (``'ref@cpu'``,
    ``'pallas-fused|compiled@TPU v5 lite'``).

    Backends without an ``interpret`` knob key by name alone; pallas
    backends split interpreter vs compiled timings into separate classes
    (``'pallas-fused|interpret'``) because the two are orders of magnitude
    apart — one fitted line cannot cover both.  The ``@device_kind``
    qualifier keeps timings from different silicon apart the same way — a
    v4 fit must not predict for a v5e.  Lookup falls back to the
    unqualified class (`CostTable.coeffs`), so pre-qualification tables
    keep working.
    """
    from repro import kernels
    from . import backends as backends_mod
    spec = backends_mod.get(backend)
    if "interpret" not in spec.knobs:
        base = backend
    else:
        mode = ("interpret" if kernels.resolve_interpret(interpret)
                else "compiled")
        base = f"{backend}|{mode}"
    return f"{base}@{kernels.device_kind()}"


# --- the fitted model --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClassCoeffs:
    """Fitted fill-cost coefficients of one backend class (seconds).

    One fill call of B scenarios costs::

        t_fill = c_fixed
               + c_eval_dim  * (B * n_cap * d)       # per-eval-coordinate work
               + c_chunk     * (B * n_chunks)        # per-scan-step overhead
               + c_tile_step * (B * n_cap / tile)    # pallas grid steps only

    ``c_eval_dim`` is what makes chunk-induced ``n_cap`` padding
    (`VegasConfig.resolve` rounds ``n_cap`` up to a chunk multiple) a
    first-class cost; ``c_chunk`` is what keeps the tuner from collapsing to
    tiny chunks.  ``iter_overhead_s`` covers the non-fill part of an
    iteration (map/stratification adaptation + aggregation) per scenario.
    Every coefficient is non-negative by construction (:func:`_nnls`), so
    predictions are monotone in each work term.
    """
    c_fixed: float = 0.0
    c_eval_dim: float = 0.0
    c_chunk: float = 0.0
    c_tile_step: float = 0.0
    iter_overhead_s: float = 0.0
    n_samples: int = 0

    def fill_s(self, *, b: int, d: int, n_cap: int, n_chunks: int,
               tile: int | None = None) -> float:
        t = (self.c_fixed + self.c_eval_dim * b * n_cap * d
             + self.c_chunk * b * n_chunks)
        if tile:
            t += self.c_tile_step * (b * n_cap / tile)
        return t

    def iteration_s(self, *, b: int, d: int, n_cap: int, n_chunks: int,
                    tile: int | None = None) -> float:
        return (self.fill_s(b=b, d=d, n_cap=n_cap, n_chunks=n_chunks,
                            tile=tile) + self.iter_overhead_s * b)


#: Order-of-magnitude CPU constants (fitted on a 1-core CPU dev box) — the
#: fallback when no calibrated table is found, so ``autotune=True`` degrades
#: to sensible relative knob choices rather than an error.  Absolute
#: magnitudes only matter relative to each other: c_chunk/c_eval_dim sets
#: the padding-vs-scan-overhead tradeoff that picks the chunk.
BUILTIN_CLASSES: Mapping[str, ClassCoeffs] = {
    "ref": ClassCoeffs(c_fixed=2e-3, c_eval_dim=2e-7, c_chunk=1e-3,
                       iter_overhead_s=1e-3),
    "pallas|interpret": ClassCoeffs(c_fixed=5e-3, c_eval_dim=2e-5,
                                    c_chunk=5e-3, c_tile_step=2e-4,
                                    iter_overhead_s=1e-3),
    "pallas-fused|interpret": ClassCoeffs(c_fixed=5e-3, c_eval_dim=2e-6,
                                          c_chunk=2e-3, c_tile_step=2e-4,
                                          iter_overhead_s=1e-3),
    # Compiled-Mosaic estimates (no TPU in the calibration loop yet): the
    # per-eval term drops ~3 orders of magnitude and the per-grid-step term
    # dominates, which is exactly the regime the static VMEM autotune's
    # largest-tile preference encodes.
    "pallas|compiled": ClassCoeffs(c_fixed=1e-4, c_eval_dim=5e-10,
                                   c_chunk=2e-5, c_tile_step=2e-6,
                                   iter_overhead_s=2e-4),
    "pallas-fused|compiled": ClassCoeffs(c_fixed=1e-4, c_eval_dim=2e-10,
                                         c_chunk=2e-5, c_tile_step=2e-6,
                                         iter_overhead_s=2e-4),
}


@dataclasses.dataclass(frozen=True)
class CostTable:
    """Per-device fitted cost tables: one :class:`ClassCoeffs` per backend
    class, keyed by the environment that produced them."""
    device_kind: str = "unknown"
    jax_backend: str = "unknown"
    git_sha: str = "unknown"
    source: str = "builtin"       # builtin | calibrated | a file path
    calibration_wall_s: float = 0.0
    classes: Mapping[str, ClassCoeffs] = dataclasses.field(
        default_factory=dict)

    def coeffs(self, key: str) -> ClassCoeffs:
        """Coefficients for a class, falling back exact -> device-qualified
        sibling mode -> unqualified -> unqualified sibling -> builtin -> ref
        so prediction never KeyErrors (an uncalibrated class still gets
        order-of-magnitude-sane relative choices, and a table calibrated
        before device qualification keeps serving qualified lookups)."""
        base, _, dev = key.partition("@")
        sib = None
        if "|" in base:
            name, mode = base.split("|", 1)
            sib = f"{name}|{'compiled' if mode == 'interpret' else 'interpret'}"
        tries = [key]
        if dev:
            if sib:
                tries.append(f"{sib}@{dev}")
            tries.append(base)
        if sib:
            tries.append(sib)
        for k in tries:
            got = self.classes.get(k)
            if got is not None:
                return got
        return BUILTIN_CLASSES.get(base) or BUILTIN_CLASSES["ref"]

    def to_json(self) -> dict:
        return {
            "device_kind": self.device_kind,
            "jax_backend": self.jax_backend,
            "git_sha": self.git_sha,
            "source": self.source,
            "calibration_wall_s": round(self.calibration_wall_s, 3),
            "classes": {k: dataclasses.asdict(v)
                        for k, v in self.classes.items()},
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)
        return path

    @classmethod
    def load(cls, path: str) -> "CostTable":
        with open(path) as f:
            obj = json.load(f)
        classes = {k: ClassCoeffs(**v)
                   for k, v in obj.get("classes", {}).items()}
        return cls(device_kind=obj.get("device_kind", "unknown"),
                   jax_backend=obj.get("jax_backend", "unknown"),
                   git_sha=obj.get("git_sha", "unknown"),
                   source=path,
                   calibration_wall_s=obj.get("calibration_wall_s", 0.0),
                   classes=classes)


BUILTIN_TABLE = CostTable(classes=BUILTIN_CLASSES)


def resolve_table(cost_table: Any = None) -> CostTable:
    """Find the cost table for this process, in priority order:

      1. ``cost_table`` (an `ExecutionConfig.cost_table`: a `CostTable` or a
         path string);
      2. ``$REPRO_COST_TABLE`` (:data:`TABLE_ENV`);
      3. ``./COST_TABLE.json`` (:data:`DEFAULT_TABLE_PATH`);
      4. the builtin order-of-magnitude table.

    A missing/unreadable explicit path raises; the implicit fallbacks are
    silent (autotuning must work out of the box).
    """
    if isinstance(cost_table, CostTable):
        return cost_table
    if isinstance(cost_table, str):
        return CostTable.load(cost_table)
    env = os.environ.get(TABLE_ENV)
    if env:
        return CostTable.load(env)
    if os.path.exists(DEFAULT_TABLE_PATH):
        try:
            return CostTable.load(DEFAULT_TABLE_PATH)
        except (OSError, ValueError, KeyError, TypeError):
            return BUILTIN_TABLE
    return BUILTIN_TABLE


# --- fitting -----------------------------------------------------------------

def _nnls(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Non-negative least squares without scipy: solve OLS on the active
    feature set, drop the most negative coefficient, repeat.  Exact enough
    for our tiny (<= 4-column) designs, and it guarantees the monotone
    predictions the chooser relies on."""
    active = list(range(x.shape[1]))
    coef = np.zeros(x.shape[1])
    while active:
        c, *_ = np.linalg.lstsq(x[:, active], y, rcond=None)
        if (c >= 0.0).all():
            coef[np.asarray(active)] = c
            break
        active.pop(int(np.argmin(c)))
    return coef


def fit_class(samples: list[dict]) -> ClassCoeffs:
    """Fit one class's coefficients from calibration samples (dicts with
    ``b, d, n_cap, n_chunks, tile (or None), seconds``)."""
    has_tile = any(s.get("tile") for s in samples)
    rows, y = [], []
    for s in samples:
        b = s.get("b", 1)
        row = [1.0, b * s["n_cap"] * s["d"], b * s["n_chunks"]]
        if has_tile:
            row.append(b * s["n_cap"] / s["tile"] if s.get("tile") else 0.0)
        rows.append(row)
        y.append(s["seconds"])
    coef = _nnls(np.asarray(rows, np.float64), np.asarray(y, np.float64))
    return ClassCoeffs(
        c_fixed=float(coef[0]), c_eval_dim=float(coef[1]),
        c_chunk=float(coef[2]),
        c_tile_step=float(coef[3]) if has_tile else 0.0,
        n_samples=len(samples))


# --- calibration -------------------------------------------------------------

#: The calibration grids: small enough that fast mode completes in ~1 minute
#: on one CPU core (pallas-interpret fill costs ~0.2 ms/eval, which is why
#: its shapes are tiny), varied enough that every fitted feature moves.
_REF_GRID_FAST = dict(dims=(4, 10), nevals=(16_384, 65_536),
                      chunks=(1_024, 4_096, 16_384))
_REF_GRID_FULL = dict(dims=(4, 6, 10), nevals=(16_384, 65_536, 262_144),
                      chunks=(1_024, 4_096, 16_384, 65_536))
_PALLAS_GRID_FAST = dict(dims=(4,), nevals=(1_024, 4_096),
                         chunks=(512, 1_024), tiles=(64, 256))
_PALLAS_GRID_FULL = dict(dims=(4,), nevals=(1_024, 4_096, 16_384),
                         chunks=(512, 1_024, 4_096), tiles=(32, 128, 512))


def _time_steady(fn, *args, repeats: int = 2) -> float:
    """Median steady-state wall of ``fn(*args)``: one warmup call pays
    trace+compile, the measured repeats reuse the executable — the regime a
    long-lived run/service amortizes into, and the one the knobs actually
    move (compile time is knob-insensitive noise several times larger than
    the per-call effects being fitted)."""
    import time as _time
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(_time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _fill_sample(backend: str, dim: int, neval: int, chunk: int,
                 tile: int | None, *, ninc: int = 64,
                 repeats: int = 2) -> dict:
    """Time one jitted steady-state fill of one (backend, shape, tile)
    point; returns the fitted-feature sample."""
    import functools

    import jax

    from repro.core import integrator as core
    from repro.core import map as vmap_
    from repro.core import strat
    from repro.core.integrands import make_cosine
    from .config import ExecutionConfig
    from . import backends as backends_mod

    execution = ExecutionConfig(backend=backend, tile=tile)
    cfg = core.VegasConfig(neval=neval, ninc=ninc, chunk=chunk,
                           execution=execution)
    rcfg = cfg.resolve(dim)
    ig = make_cosine(dim=dim)
    fill_fn = backends_mod.bind_fill(rcfg, backend=backend)
    edges = vmap_.uniform_edges(ig.lower, ig.upper, rcfg.ninc, rcfg.dtype)
    n_h = strat.uniform_nh(rcfg.neval, rcfg.n_cubes)
    key = jax.random.PRNGKey(0)
    prog = jax.jit(functools.partial(
        lambda e, n, k, f: f(e, n, k, ig), f=fill_fn))
    seconds = _time_steady(prog, edges, n_h, key, repeats=repeats)
    return dict(b=1, d=dim, n_cap=rcfg.n_cap,
                n_chunks=rcfg.n_cap // rcfg.chunk, tile=tile,
                chunk=rcfg.chunk, neval=neval, seconds=seconds)


def _iter_overhead(dim: int = 4, neval: int = 16_384,
                   chunk: int = 4_096) -> float:
    """Per-scenario non-fill iteration cost: time one full jitted
    `iteration_step` and subtract the same-shape fill.  Backend-independent
    (adaptation/aggregation never touch the kernel), so one measurement
    serves every class."""
    import functools

    import jax

    from repro.core import integrator as core
    from repro.core.integrands import make_cosine

    cfg = core.VegasConfig(neval=neval, ninc=64, chunk=chunk)
    rcfg = cfg.resolve(dim)
    ig = make_cosine(dim=dim)
    state = core.init_state(ig, rcfg, jax.random.PRNGKey(0))
    step = jax.jit(functools.partial(core.iteration_step, integrand=ig,
                                     cfg=rcfg))
    t_step = _time_steady(step, state)
    t_fill = _fill_sample("ref", dim, neval, chunk, None)["seconds"]
    return max(t_step - t_fill, 0.0)


def calibrate(*, fast: bool = True, backends: tuple[str, ...] | None = None,
              repeats: int = 2) -> CostTable:
    """Measure the fill/adapt hot paths over the calibration grid and fit a
    :class:`CostTable` for the current device.

    ``backends=None`` calibrates every registry backend in its
    platform-resolved execution mode (interpreted pallas off TPU, compiled
    on TPU).
    """
    import time as _time

    import jax

    from repro import kernels
    from . import backends as backends_mod

    t0 = _time.perf_counter()
    if backends is None:
        backends = backends_mod.available()
    overhead = _iter_overhead()
    classes: dict[str, ClassCoeffs] = {}
    for backend in backends:
        spec = backends_mod.get(backend)
        key = class_key(backend)
        grid = ((_PALLAS_GRID_FAST if fast else _PALLAS_GRID_FULL)
                if "tile" in spec.knobs
                else (_REF_GRID_FAST if fast else _REF_GRID_FULL))
        samples = []
        for d in grid["dims"]:
            for neval in grid["nevals"]:
                for chunk in grid["chunks"]:
                    for tile in grid.get("tiles", (None,)):
                        s = _fill_sample(backend, d, neval, chunk, tile,
                                         repeats=repeats)
                        s["class"] = key
                        samples.append(s)
        classes[key] = dataclasses.replace(fit_class(samples),
                                           iter_overhead_s=overhead)
    return CostTable(device_kind=kernels.device_kind(),
                     jax_backend=jax.default_backend(), git_sha=_git_sha(),
                     source="calibrated",
                     calibration_wall_s=_time.perf_counter() - t0,
                     classes=classes)


# --- prediction --------------------------------------------------------------

def predict_run_s(coeffs: ClassCoeffs, rcfg, *, b: int = 1,
                  tile: int | None = None, n_shards: int = 1) -> float:
    """Predicted whole-run wall (seconds) of ``max_it`` iterations at one
    knob combination.  Sharding divides the chunk range (`shard_chunk_range`
    ceil semantics: the critical path is the largest shard's chunk count);
    the O(KB) adaptation state is replicated, so ``iter_overhead_s`` does
    not shrink with the mesh."""
    n_chunks = rcfg.n_cap // rcfg.chunk
    shard_chunks = -(-n_chunks // max(n_shards, 1))
    fill = coeffs.fill_s(b=b, d=rcfg.dim, n_cap=shard_chunks * rcfg.chunk,
                         n_chunks=shard_chunks, tile=tile)
    return rcfg.max_it * (fill + coeffs.iter_overhead_s * b)


# --- the knob chooser --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneReport:
    """What the tuner decided and why (`Plan.describe` renders this)."""
    class_key: str
    table_source: str
    device_kind: str
    chosen: Mapping[str, Any]
    default: Mapping[str, Any]
    predicted_s: float
    predicted_default_s: float

    def describe(self) -> str:
        def fmt(knobs):
            return " ".join(f"{k}={v}" for k, v in knobs.items()
                            if v is not None)
        # class_key already carries the live @device_kind qualifier; the
        # device_kind FIELD is the table's own provenance, shown only via
        # table= (a builtin table reports 'unknown').
        same = dict(self.chosen) == dict(self.default)
        return (f"autotuned[{self.class_key}, "
                f"table={self.table_source}] "
                f"{fmt(self.chosen)} (predicted {self.predicted_s:.3g}s"
                + (", same as default" if same else
                   f" vs default {fmt(self.default)} "
                   f"{self.predicted_default_s:.3g}s") + ")")


def _is_family(workload) -> bool:
    # Same duck-typing as make_plan's batch-axis check.
    return hasattr(workload, "params") and hasattr(workload, "bind")


def _accum_itemsize(execution) -> int:
    """Accumulator byte width the budget oracles must price (§15): 8 under a
    widened f64 PrecisionPolicy, else 4."""
    prec = getattr(execution, "precision", None)
    if prec is not None and prec.accum_dtype is not None:
        return int(np.dtype(prec.accum_dtype).itemsize)
    return 4


def _tile_candidates(chunk: int, d: int, ninc: int, n_cubes: int,
                     accum_itemsize: int = 4) -> list:
    """A small predicted-orderable subset of the kernel's valid tiles: the
    static-autotune choice plus the power-of-two divisors >= 8.  All
    candidates come from the kernel's own validity oracle
    (``ops.valid_tiles``), so the tuner can never pick a tile
    ``_pick_tile`` rejects — including under a widened policy, where the
    8-byte accumulators shrink the valid set (``accum_itemsize``)."""
    from repro.kernels import ops
    valid = ops.valid_tiles(chunk, d, ninc, n_cubes,
                            accum_itemsize=accum_itemsize)
    if not valid:
        return [None]     # let the kernel's own picker raise its diagnostic
    pow2 = [t for t in valid if t >= 8 and (t & (t - 1)) == 0]
    cands = sorted(set(pow2[-3:]) | {valid[-1]}, reverse=True)
    return cands or [valid[-1]]


def tune(workload, cfg, *, table: CostTable | None = None):
    """Choose chunk/tile/batch/shard knobs for ``(workload, cfg)`` by
    minimizing the measured cost model over valid combinations.

    Returns ``(tuned_cfg, TuneReport | None)``.  The tuned config has
    ``autotune=False`` with every chosen knob pinned, so re-planning it is
    deterministic and cheap.  Pinned knobs are respected: an explicit
    ``tile=...`` or ``shard_axes=...`` is never overridden, and the caller's
    own ``chunk`` is always in the candidate set (the tuner deviates only
    when the model predicts a strict win; ties keep the default).  If the
    backend is unknown, the config is returned unchanged so ``make_plan``
    raises its own diagnostic.
    """
    from repro.core import strat
    from . import backends as backends_mod
    from . import sharding as sharding_mod
    from .plan import PlanError, make_plan

    execution = cfg.execution
    try:
        spec = backends_mod.get(execution.backend)
    except KeyError:
        return cfg, None
    if table is None:
        table = resolve_table(execution.cost_table)
    key = class_key(spec.name, execution.interpret)
    coeffs = table.coeffs(key)
    dim = workload.dim
    family = _is_family(workload)
    b = workload.batch_size if family else 1
    probe_exec = dataclasses.replace(execution, autotune=False)
    tiled = "tile" in spec.knobs
    pinned_tile = execution.tile if tiled else None
    itemsize = _accum_itemsize(execution)

    # The default-knob baseline the report compares against.
    base_rcfg = cfg.resolve(dim)
    default_tile = pinned_tile
    if tiled and default_tile is None:
        from repro.kernels import ops
        default_tile = ops.autotune_tile(base_rcfg.chunk, dim,
                                         base_rcfg.ninc, base_rcfg.n_cubes,
                                         accum_itemsize=itemsize)
    mesh = execution.mesh
    default_axes = (execution.shard_axes if execution.shard_axes is not None
                    else (tuple(mesh.axis_names) if mesh is not None else None))
    default_shards = (sharding_mod.mesh_shard_count(mesh, default_axes)
                      if mesh is not None else 1)
    vmappable = spec.supports(backends_mod.VMAPPABLE)
    default_batch = execution.batch
    default_vmap = family and (default_batch == "vmap" or (
        default_batch == "auto" and vmappable))

    def predict(rcfg, tile, n_shards, vmapped):
        if vmapped or not family:
            return predict_run_s(coeffs, rcfg, b=b, tile=tile,
                                 n_shards=n_shards)
        # Serial family: B independent programs, each paying c_fixed +
        # overhead on its own.
        return b * predict_run_s(coeffs, rcfg, b=1, tile=tile,
                                 n_shards=n_shards)

    predicted_default = predict(base_rcfg, default_tile, default_shards,
                                default_vmap)

    # --- candidate enumeration ----------------------------------------------
    ns = cfg.nstrat or strat.choose_nstrat(cfg.neval, dim, cfg.max_cubes)
    n_cubes = ns ** dim
    raw_cap = strat.eval_capacity(cfg.neval, n_cubes)
    chunk_cands = sorted({c for c in CHUNK_CANDIDATES
                          if c <= max(raw_cap, 256)} | {cfg.chunk})
    axes_cands: list = [execution.shard_axes]
    if mesh is not None and execution.shard_axes is None:
        axes_cands = [tuple(mesh.axis_names)]
        if len(mesh.axis_names) > 1:
            axes_cands += [(a,) for a in mesh.axis_names]
    batch_cands = ([execution.batch] if not family
                   or execution.batch != "auto" or not vmappable
                   else ["vmap", "serial"])

    combos = []
    for chunk in chunk_cands:
        ccfg = dataclasses.replace(cfg, chunk=chunk, execution=probe_exec)
        rcfg = ccfg.resolve(dim)
        tiles = ([pinned_tile] if not tiled or pinned_tile is not None
                 else _tile_candidates(rcfg.chunk, dim, rcfg.ninc,
                                       rcfg.n_cubes, itemsize))
        for tile in tiles:
            for axes in axes_cands:
                n_sh = (sharding_mod.mesh_shard_count(mesh, axes)
                        if mesh is not None and axes else 1)
                for bm in batch_cands:
                    pred = predict(rcfg, tile, n_sh, bm != "serial")
                    combos.append((pred, chunk, tile, axes, bm))
    # Stable sort on predicted cost alone: equal predictions keep candidate
    # order, and the caller's own chunk sorts via its position in the sorted
    # candidate list — deterministic for a fixed table (property-tested).
    combos.sort(key=lambda c: c[0])

    # --- probe: validity is make_plan's, not ours ---------------------------
    for pred, chunk, tile, axes, bm in combos:
        # A tile on a backend without the knob is forwarded unchanged (it
        # rides along inside probe_exec) so the probe — and the fallback —
        # surface make_plan's own knob PlanError: the tuner must never
        # launder an invalid pin into a valid plan.
        cand_exec = dataclasses.replace(
            probe_exec, shard_axes=axes, batch=bm,
            **({"tile": tile} if tiled else {}))
        cand_cfg = dataclasses.replace(cfg, chunk=chunk,
                                       execution=cand_exec)
        try:
            make_plan(workload, cand_cfg)
        except PlanError:
            continue
        report = TuneReport(
            class_key=key, table_source=table.source,
            device_kind=table.device_kind,
            chosen=dict(chunk=cand_cfg.resolve(dim).chunk, batch=bm,
                        shard_axes=axes,
                        **({"tile": tile} if tiled else {})),
            default=dict(chunk=base_rcfg.chunk, batch=execution.batch,
                         shard_axes=execution.shard_axes,
                         **({"tile": default_tile} if tiled else {})),
            predicted_s=pred, predicted_default_s=predicted_default)
        return cand_cfg, report
    # Nothing the model proposed validates (e.g. an exotic workload the
    # probes cannot satisfy): fall back to the caller's own knobs — by
    # construction make_plan accepts them iff it would have without
    # autotune, so autotuning never rejects a plan explicit knobs admit.
    return cfg.with_execution(probe_exec), None


# --- the serving layer's shared cost model -----------------------------------

class OnlineCost:
    """Per-class per-scenario-iteration cost for the sweep service (§12).

    Exactly the PR-7 semantics for observations: ``observe`` keeps the
    MINIMUM ``wall / (trips * B)`` ever measured for a class, so
    trace+compile-inflated samples (a class's calibration batch) never
    poison the estimate upward.  A :class:`CostTable`, when given, serves
    only as the PRIOR for classes with no observation yet — a request's
    FIRST batch can then already be budget-enforced.  Without a table the
    behavior is bit-identical to the legacy dict (first batch
    uncalibrated)."""

    def __init__(self, table: CostTable | None = None):
        self.table = table
        self._observed: dict[tuple, float] = {}

    def observe(self, key: tuple, unit_s: float) -> None:
        old = self._observed.get(key)
        self._observed[key] = (unit_s if old is None
                               else min(old, unit_s))

    def unit(self, key: tuple, *, rcfg=None, backend: str = "ref",
             interpret: bool | None = None,
             tile: int | None = None) -> float | None:
        """Per-scenario-iteration seconds: the min-observed value, else the
        table prediction (when a table and the plan geometry are given),
        else None (uncalibrated — budgets unenforced, legacy behavior)."""
        got = self._observed.get(key)
        if got is not None or self.table is None or rcfg is None:
            return got
        try:
            coeffs = self.table.coeffs(class_key(backend, interpret))
        except KeyError:
            return None
        return coeffs.iteration_s(b=1, d=rcfg.dim, n_cap=rcfg.n_cap,
                                  n_chunks=rcfg.n_cap // rcfg.chunk,
                                  tile=tile)

    @property
    def classes_calibrated(self) -> int:
        return len(self._observed)

    def snapshot(self, limit: int = 8) -> dict:
        return {str(k[0]): v
                for k, v in list(self._observed.items())[:limit]}
