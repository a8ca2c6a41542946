"""CLI driver for the VEGAS+ engine (the paper's workload).

  PYTHONPATH=src python -m repro.launch.integrate --integrand ridge \
      --neval 1000000 --iters 20 --config def --backend pallas-fused

Execution axes (backend / sharding / checkpointing / stopping) map 1:1 onto
the unified ``repro.engine.ExecutionConfig``; ``--rtol``/``--atol`` set a
`StopPolicy` convergence target (the run stops once the combined sdev meets
it, reported as ``n_it_used``); ``--plan`` prints the validated plan
(backend capabilities, shard count, loop mode) without running it.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs.vegas import PAPER_CONFIGS
from repro.core import VegasConfig
from repro.core import integrands as igs
from repro.engine import (CheckpointPolicy, ExecutionConfig, GradPolicy,
                          PrecisionPolicy, StopPolicy, available, execute,
                          make_plan)
from repro.launch import env

INTEGRANDS = {
    "sine_exp": igs.make_sine_exp,
    "linear": igs.make_linear,
    "cosine": igs.make_cosine,
    "exponential": igs.make_exponential,
    "roos_arnold": igs.make_roos_arnold,
    "morokoff_caflisch": igs.make_morokoff_caflisch,
    "gaussian": igs.make_gaussian,
    "ridge": igs.make_ridge,
    "asian": igs.make_asian_option,
    "asian_geo": lambda: igs.make_asian_option(geometric=True),
    "feynman": igs.make_feynman_path,
}


def add_execution_args(ap: argparse.ArgumentParser) -> None:
    """The shared execution-axis flags (integrate + sweep CLIs)."""
    ap.add_argument("--backend",
                    choices=sorted(available()) + ["auto"], default="auto",
                    help="fill backend from the engine registry "
                         "(pallas-fused = P-V3 streaming kernel; auto = "
                         "platform default via backends.backend_default)")
    ap.add_argument("--interpret", choices=["auto", "true", "false"],
                    default="auto",
                    help="pallas execution mode; auto = compiled by "
                         "Mosaic on TPU, interpreter elsewhere "
                         "(kernels.resolve_interpret)")
    ap.add_argument("--tile", type=int, default=None,
                    help="pallas TPU tile override (default: VMEM autotune)")
    ap.add_argument("--accum-dtype", choices=["float32", "float64"],
                    default=None,
                    help="accumulation dtype (§15 PrecisionPolicy): widen "
                         "the moment accumulators without changing the "
                         "sample dtype (float64 needs JAX_ENABLE_X64=1 / "
                         "--x64; validated at plan time against the "
                         "backend's declared precision pairs)")
    ap.add_argument("--autotune", action="store_true",
                    help="pick chunk/tile/batch/shard knobs from the "
                         "measured cost model (engine.autotune, §13); "
                         "combine with --plan to see the chosen knobs and "
                         "predicted vs default cost without running")
    ap.add_argument("--cost-table", default=None, metavar="PATH",
                    help="calibrated cost table for --autotune (default: "
                         "$REPRO_COST_TABLE, then ./COST_TABLE.json, then "
                         "the builtin order-of-magnitude table)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the fill over all local devices "
                         "(launch.mesh.make_local_mesh)")
    ap.add_argument("--rtol", type=float, default=0.0,
                    help="stop once combined sdev <= rtol * |mean| "
                         "(adaptive while_loop; 0 = fixed-length loop)")
    ap.add_argument("--atol", type=float, default=0.0,
                    help="stop once combined sdev <= atol "
                         "(combines with --rtol as max(rtol*|mean|, atol))")
    ap.add_argument("--min-it", type=int, default=2,
                    help="never stop before this many iterations")
    ap.add_argument("--grad", choices=["off", "pathwise", "score"],
                    default="off",
                    help="differentiable two-phase run (repro.grad, §11): "
                         "adapt with gradients stopped, then a frozen-map "
                         "eval pass; reports d(estimate)/d(params, bounds)")
    ap.add_argument("--no-grad-sdev", action="store_true",
                    help="skip the per-component gradient error bars "
                         "(the derivative-integrand passes)")
    ap.add_argument("--plan", action="store_true",
                    help="print the validated execution plan and exit")
    env.add_env_args(ap)


def build_execution(args, **extra) -> ExecutionConfig:
    # interpret/tile are forwarded as given; the plan
    # validator rejects them loudly when the chosen backend declares no
    # such knob.
    interpret = {"auto": None, "true": True, "false": False}[args.interpret]
    mesh = None
    if args.shard:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
    # Any nonzero tolerance builds a policy — including a negative typo,
    # which must reach make_plan's non-negative validation (PlanError),
    # not be silently dropped here.
    stop = (StopPolicy(rtol=args.rtol, atol=args.atol, min_it=args.min_it)
            if (args.rtol != 0 or args.atol != 0) else None)
    grad = (GradPolicy(mode=args.grad, with_sdev=not args.no_grad_sdev)
            if args.grad != "off" else None)
    precision = (PrecisionPolicy(accum_dtype=args.accum_dtype)
                 if getattr(args, "accum_dtype", None) else None)
    return ExecutionConfig(backend=args.backend, interpret=interpret,
                           tile=args.tile, mesh=mesh, stop=stop,
                           grad=grad, autotune=args.autotune,
                           cost_table=args.cost_table, precision=precision,
                           **extra)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--integrand", choices=list(INTEGRANDS), default="ridge")
    ap.add_argument("--neval", type=int, default=500_000)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--skip", type=int, default=5)
    ap.add_argument("--config", choices=["def", "vf", "tq"], default="def")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="checkpoint VegasState into DIR every iteration "
                         "(forces the host loop)")
    ap.add_argument("--seed", type=int, default=0)
    add_execution_args(ap)
    args = ap.parse_args(argv)
    env.apply_env_args(args)
    env.use_compile_cache()

    ig = INTEGRANDS[args.integrand]()
    base = PAPER_CONFIGS[args.config]
    execution = build_execution(
        args, checkpoint=(CheckpointPolicy(directory=args.checkpoint)
                          if args.checkpoint else None))
    cfg = VegasConfig(neval=args.neval, max_it=args.iters, skip=args.skip,
                      ninc=base.ninc, alpha=base.alpha, beta=base.beta,
                      execution=execution)
    plan = make_plan(ig, cfg)
    if args.plan:
        print(plan.describe())
        return plan
    t0 = time.time()
    res = execute(plan, key=jax.random.PRNGKey(args.seed))
    dt = time.time() - t0
    print(f"integrand={ig.name} dim={ig.dim} config={args.config} "
          f"[{execution.describe()}]")
    if plan.grad is not None:
        # GradResult: the frozen-map eval estimate + boundary sensitivities.
        print(f"  result  = {res.mean:.8g} +- {res.sdev:.3g} "
              f"(mode={res.mode}, {res.n_it_used} adapt iterations)")
        for j in range(ig.dim):
            print(f"  d/d bounds[{j}]  lower {res.grad_lower[j]:+.5g}  "
                  f"upper {res.grad_upper[j]:+.5g}")
    else:
        print(f"  result  = {res.mean:.8g} +- {res.sdev:.3g} "
              f"(chi2/dof {res.chi2_dof:.2f}, {res.n_it} combined, "
              f"{res.n_it_used}/{args.iters} iterations executed)")
    if ig.target is not None:
        pull = (res.mean - ig.target) / max(res.sdev, 1e-30)
        print(f"  target  = {ig.target:.8g}  pull = {pull:+.2f} sigma")
    print(f"  wall    = {dt:.2f}s  ({args.neval * args.iters / dt:,.0f} evals/s)")
    return res


if __name__ == "__main__":
    main()
