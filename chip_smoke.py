#!/usr/bin/env python3
"""Smoke run of the VEGAS+ main path on TPU: the quickest proof that the
system still starts, compiles its Mosaic fill kernel and integrates right
on the chip.

    python chip_smoke.py              # one chip, four phases
    python chip_smoke.py --chips 4    # the 4-chip sharded fill vs one chip

One chip (the default):
  1. single integrals through ``make_plan``/``execute`` with
     ``backend='auto'`` at the paper's ``def`` configuration (ninc 1024,
     alpha 0.5, beta 0.75), 1e7 evaluations per iteration, 10 iterations,
     skip 2: ``roos_arnold`` d=10, ``gaussian`` d=4 (sigma 0.01) and
     ``ridge`` d=4 with 1000 peaks.  Fails unless the plan resolved to
     ``pallas-fused`` with the kernel compiled (no interpreter), or if
     |pull| against the analytic target exceeds 5;
  2. the fused kernel against the ``ref`` oracle on the chip: one fill of
     ``gaussian`` and of ``ridge`` on their adapted maps, same key.  Both
     draw bit-identical samples, so every accumulator must agree to f32
     accumulation-order rounding (``ORACLE_RTOL``/``ORACLE_ATOL``);
  3. ``run_batch`` over the gaussian family, B=64 scenarios at 1e6
     evaluations, one vmapped program on the fused kernel;
  4. a ``SweepService`` answering 8 requests on the platform-default
     backend.

``--chips 4`` runs only the comparison that needs a mesh: one ``gaussian``
d=4 fill at 1e8 evaluations sharded over the four local chips, against the
same fill on one of them.  Per accumulator they agree to the
device-count-invariance contract (Kahan-compensated shards,
``SHARD_ULPS``).

Every phase prints one line.  Its times are smoke timings, not
measurements: one host-clock run, with ``compile`` summed from JAX's own
lowering and backend-compile events and ``run`` the rest of the wall time.
The last line of standard output is the JSON contract line naming the
device.  A failed phase exits non-zero and the contract line is not
printed.  One process holds the chip throughout; nothing here starts
another.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

NEVAL = 10_000_000          # evaluations per iteration, phase 1
ITERS, SKIP = 10, 2
SWEEP_B, SWEEP_NEVAL = 64, 1_000_000
SERVE_REQUESTS, SERVE_NEVAL = 8, 1_000_000
SHARD_NEVAL = 100_000_000
MAX_PULL = 5.0
#: Fused vs ref on identical samples: the interpret-mode parity suite's
#: f32 accumulation-order tolerance (tests/test_fill_parity.py), per
#: element |fused - ref| <= ORACLE_RTOL*|ref| + ORACLE_ATOL*max|ref|.
ORACLE_RTOL, ORACLE_ATOL = 1e-4, 1e-5
#: Sharded vs one-chip fill: each accumulator within this many f32 ulps.
SHARD_ULPS = 4

FIELDS = ("map_sums", "map_counts", "cube_s1", "cube_s2")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Failure(SystemExit):
    """A phase whose result is wrong: exit 1 with the reason on stderr."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL {msg}")


class CompileClock:
    """Seconds JAX spent lowering and compiling, from its own events."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def time(self, fn):
        """``(result, compile_s, run_s)`` of ``fn()``; run = wall - compile."""
        c0, t0 = self.seconds, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        compiled = self.seconds - c0
        return out, compiled, wall - compiled


def _pull(mean, sdev, target) -> float:
    return (mean - target) / max(sdev, 1e-30)


def _check_plan(plan, kernels):
    mode = ("interpret" if kernels.resolve_interpret(
        plan.execution.interpret, plan.backend.family) else "compiled")
    if plan.backend.name != "pallas-fused" or mode != "compiled":
        raise Failure(f"plan resolved to backend={plan.backend.name} "
                      f"pallas={mode}, not compiled pallas-fused")
    return mode


def _check_kernel(program, *args):
    """The lowered program holds the Mosaic kernel, not an XLA stand-in."""
    if "tpu_custom_call" not in program.lower(*args).as_text():
        raise Failure("no Mosaic kernel in the lowered program")


def single_integrals(ctx):
    """Phase 1: returns {name: (integrand, plan, result)} for phase 2."""
    import jax
    from repro import kernels
    from repro.configs.vegas import PAPER_CONFIGS
    from repro.core import VegasConfig
    from repro.core import integrands as igs
    from repro.engine import ExecutionConfig, execute, make_plan
    from repro.engine.executor import make_single_program
    from repro.core.integrator import init_state
    from repro.kernels import ops as kops

    base = PAPER_CONFIGS["def"]
    out = {}
    for ig in (igs.make_roos_arnold(dim=10), igs.make_gaussian(dim=4),
               igs.make_ridge(dim=4, n_peaks=1000)):
        cfg = VegasConfig(neval=NEVAL, max_it=ITERS, skip=SKIP,
                          ninc=base.ninc, alpha=base.alpha, beta=base.beta,
                          execution=ExecutionConfig(backend="auto"))
        plan = make_plan(ig, cfg)
        mode = _check_plan(plan, kernels)
        rc = plan.cfg
        key = jax.random.PRNGKey(0)
        _check_kernel(make_single_program(plan), init_state(ig, rc, key))
        tile = kops.autotune_tile(rc.chunk, ig.dim, rc.ninc, rc.n_cubes,
                                  row_bytes=kops.eval_row_bytes(ig, ig.dim))
        res, comp_s, run_s = ctx.clock.time(
            lambda: execute(plan, key=key))
        pull = _pull(res.mean, res.sdev, ig.target)
        print(f"phase1 {ig.name} d={ig.dim} backend={plan.backend.name} "
              f"pallas={mode} device={ctx.kind} neval={NEVAL} "
              f"iters={res.n_it_used} n_cubes={rc.n_cubes} tile={tile} "
              f"compile_s={comp_s:.2f} run_s={run_s:.2f} "
              f"mean={res.mean:.10g} +- {res.sdev:.4g} "
              f"target={ig.target:.10g} pull={pull:+.3f} "
              f"chi2/dof={res.chi2_dof:.2f} [smoke timings]", flush=True)
        if not abs(pull) <= MAX_PULL:
            raise Failure(f"{ig.name}: |pull| {abs(pull):.2f} > {MAX_PULL}")
        out[ig.name] = (ig, plan, res)
    return out


def oracle_check(ctx, runs):
    """Phase 2: fused kernel vs the ref oracle, same key, adapted maps."""
    import jax
    import numpy as np
    from repro.engine import bind_fill

    for name in ("gaussian", "ridge"):
        ig, plan, res = runs[name]
        rc = plan.cfg
        edges, n_h = res.state.edges, res.state.n_h
        key = jax.random.fold_in(jax.random.PRNGKey(1), 0)
        got = {}
        times = []
        for backend in ("pallas-fused", "ref"):
            fill = jax.jit(lambda e, nh, k, f=bind_fill(rc, backend=backend):
                           f(e, nh, k, ig))
            r, comp_s, run_s = ctx.clock.time(
                lambda: jax.block_until_ready(fill(edges, n_h, key)))
            got[backend] = r
            times.append(f"{backend}:compile_s={comp_s:.2f},run_s={run_s:.3f}")
        worst = []
        for field in FIELDS:
            a = np.asarray(getattr(got["ref"], field), np.float64)
            b = np.asarray(getattr(got["pallas-fused"], field), np.float64)
            scale = float(np.abs(a).max()) or 1.0
            err = np.abs(b - a)
            excess = err - (ORACLE_RTOL * np.abs(a) + ORACLE_ATOL * scale)
            rel = float((err / np.maximum(np.abs(a), 1e-30 * scale)).max())
            worst.append(f"{field}:max_rel={rel:.3g},"
                         f"n_diff={int((err > 0).sum())}")
            if (excess > 0).any():
                i = int(np.argmax(excess))
                raise Failure(
                    f"phase2 {name} {field}: fused {b.flat[i]!r} vs ref "
                    f"{a.flat[i]!r} at {i} exceeds rtol={ORACLE_RTOL} "
                    f"atol={ORACLE_ATOL}*max ({' '.join(worst)})")
        print(f"phase2 oracle {name} d={ig.dim} fused-vs-ref device="
              f"{ctx.kind} neval={rc.neval} tol=rtol{ORACLE_RTOL:g}+atol"
              f"{ORACLE_ATOL:g}*max {' '.join(worst)} {' '.join(times)} "
              f"[smoke timings]", flush=True)


def batched_sweep(ctx):
    """Phase 3: B=64 gaussian scenarios, one vmapped program."""
    import jax
    import numpy as np
    from repro import kernels
    from repro.batch import run_batch
    from repro.batch.family import FAMILIES
    from repro.configs.vegas import PAPER_CONFIGS
    from repro.core import VegasConfig
    from repro.engine import ExecutionConfig, make_plan

    base = PAPER_CONFIGS["def"]
    family = FAMILIES["gaussian"](SWEEP_B)
    cfg = VegasConfig(neval=SWEEP_NEVAL, max_it=ITERS, skip=SKIP,
                      ninc=base.ninc, alpha=base.alpha, beta=base.beta,
                      execution=ExecutionConfig(backend="auto"))
    plan = make_plan(family, cfg)
    mode = _check_plan(plan, kernels)
    if not plan.batched:
        raise Failure("phase3: the family plan is not one vmapped program")
    res, comp_s, run_s = ctx.clock.time(
        lambda: run_batch(family, cfg, key=jax.random.PRNGKey(2)))
    pulls = (res.mean - family.targets) / np.maximum(res.sdev, 1e-30)
    b = int(np.argmax(np.abs(pulls)))
    print(f"phase3 sweep {family.name} B={res.batch_size} d={family.dim} "
          f"backend={plan.backend.name} pallas={mode} device={ctx.kind} "
          f"neval={SWEEP_NEVAL} iters={ITERS} compile_s={comp_s:.2f} "
          f"run_s={run_s:.2f} worst_pull={pulls[b]:+.3f} (scenario {b}) "
          f"[smoke timings]", flush=True)
    if not np.all(np.isfinite(res.mean)) or not abs(pulls[b]) <= MAX_PULL:
        raise Failure(f"phase3: worst |pull| {abs(pulls[b]):.2f} > "
                      f"{MAX_PULL} or non-finite estimates")


def served_requests(ctx):
    """Phase 4: a SweepService answers 8 requests, then closes."""
    import numpy as np
    from repro import kernels
    from repro.serve import IntegrationRequest, SweepService

    backend = kernels.backend_default()
    reqs = [IntegrationRequest(family="gaussian", params=[float(p)], seed=i,
                               neval=SERVE_NEVAL, max_it=ITERS, skip=SKIP,
                               ninc=1024)
            for i, p in enumerate(np.linspace(0.2, 0.8, SERVE_REQUESTS))]

    def serve():
        with SweepService(max_batch=SERVE_REQUESTS, max_wait_s=0.05) as svc:
            tickets = [svc.submit(r) for r in reqs]
            results = [t.result(timeout=600) for t in tickets]
        return results, svc.stats()

    (results, stats), comp_s, run_s = ctx.clock.time(serve)
    pulls = np.array([_pull(float(r.mean[0]), float(r.sdev[0]),
                            float(r.targets[0])) for r in results])
    print(f"phase4 serve requests={len(results)} backend={reqs[0].backend}"
          f"->{backend} device={ctx.kind} neval={SERVE_NEVAL} "
          f"batches={stats['batches']['count']} compile_s={comp_s:.2f} "
          f"run_s={run_s:.2f} worst_pull={pulls[np.argmax(np.abs(pulls))]:+.3f}"
          f" [smoke timings]", flush=True)
    if len(results) != SERVE_REQUESTS or backend != "pallas-fused" or \
            not np.all(np.abs(pulls) <= MAX_PULL):
        raise Failure(f"phase4: {len(results)} answers on {backend}, "
                      f"pulls {np.round(pulls, 2).tolist()}")


def sharded_vs_one_chip(ctx):
    """--chips 4: the gaussian fill over a 4-chip mesh vs one chip."""
    import jax
    import numpy as np
    from repro import kernels
    from repro.configs.vegas import PAPER_CONFIGS
    from repro.core import VegasConfig
    from repro.core import integrands as igs
    from repro.core.integrator import init_state
    from repro.engine import ExecutionConfig, bind_fill, make_plan
    from repro.engine.sharding import make_sharded_fill
    from repro.launch.mesh import make_local_mesh

    if len(jax.devices()) != 4:
        raise Failure(f"--chips 4 needs 4 local chips, found "
                      f"{len(jax.devices())}")
    base = PAPER_CONFIGS["def"]
    ig = igs.make_gaussian(dim=4)
    cfg = VegasConfig(neval=SHARD_NEVAL, max_it=ITERS, skip=SKIP,
                      ninc=base.ninc, alpha=base.alpha, beta=base.beta,
                      execution=ExecutionConfig(backend="auto",
                                                mesh=make_local_mesh()))
    plan = make_plan(ig, cfg)
    mode = _check_plan(plan, kernels)
    rc = plan.cfg
    st = init_state(ig, rc, jax.random.PRNGKey(3))
    key = jax.random.fold_in(st.key, 0)
    sharded = jax.jit(lambda e, nh, k, f=make_sharded_fill(
        plan.mesh, plan.shard_axes, rc, backend=plan.backend.name):
        f(e, nh, k, ig))
    one = jax.jit(lambda e, nh, k, f=bind_fill(rc, backend=plan.backend.name,
                                               kahan=True):
                  f(e, nh, k, ig))
    s_res, s_comp, s_run = ctx.clock.time(
        lambda: jax.block_until_ready(sharded(st.edges, st.n_h, key)))
    o_res, o_comp, o_run = ctx.clock.time(
        lambda: jax.block_until_ready(one(st.edges, st.n_h, key)))
    worst = []
    for field in FIELDS:
        a = np.asarray(getattr(o_res, field))
        b = np.asarray(getattr(s_res, field))
        ulps = np.abs(b.astype(np.float64) - a) / np.spacing(
            np.maximum(np.abs(a), np.abs(b)).astype(np.float32))
        ulps = np.where(a == b, 0.0, ulps)
        worst.append(f"{field}:max_ulps={float(ulps.max()):.2f}")
        if not float(ulps.max()) <= SHARD_ULPS:
            raise Failure(f"sharded {field} off the one-chip fill by "
                          f"{float(ulps.max()):.1f} ulps > {SHARD_ULPS}")
    print(f"shard4 gaussian d={ig.dim} backend={plan.backend.name} "
          f"pallas={mode} device={ctx.kind} shards={plan.n_shards} "
          f"neval={SHARD_NEVAL} {' '.join(worst)} "
          f"sharded:compile_s={s_comp:.2f},run_s={s_run:.3f} "
          f"one_chip:compile_s={o_comp:.2f},run_s={o_run:.3f} "
          f"[smoke timings]", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-one-chip fill "
                         "comparison on a 4-chip host")
    args = ap.parse_args(argv)
    try:
        from repro.launch import env
    except ImportError as e:
        raise Failure(f"the repro package is not beside this script ({e})")
    env.use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        raise Failure(f"no TPU: JAX runs on {jax.default_backend()!r}")

    ctx = types.SimpleNamespace(
        clock=CompileClock(jax),
        kind=jax.devices()[0].device_kind.replace(" ", "_"))
    if args.chips == 4:
        sharded_vs_one_chip(ctx)
    else:
        runs = single_integrals(ctx)
        oracle_check(ctx, runs)
        batched_sweep(ctx)
        served_requests(ctx)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
