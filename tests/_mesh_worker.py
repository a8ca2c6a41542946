"""Subprocess worker for the four-device mesh tests: ``repro.core.run`` with
``ExecutionConfig(mesh=...)`` on 4 forced host devices, the way the
``gaussian_d4_e7`` deployment runs on a four-chip host, at a CPU size.

    python tests/_mesh_worker.py run    # mesh and one device, two backends
    python tests/_mesh_worker.py obs    # psum counter and scope

Prints one JSON object (the last line of standard output)."""

import os
import sys
from pathlib import Path

from repro.launch import env as launch_env

launch_env.set_host_device_count(4)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.batch import run_batch  # noqa: E402
from repro.batch.family import make_gaussian_family  # noqa: E402
from repro.core import VegasConfig, integrands, run  # noqa: E402
from repro.core import integrator as core  # noqa: E402
from repro.engine import ExecutionConfig, StopPolicy, make_plan  # noqa: E402
from repro.engine import executor  # noqa: E402
from repro.engine.executor import _plan_fill_fn  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402

sys.path.append(str(Path(__file__).resolve().parents[1] / "bench"))
import reference  # noqa: E402

#: The deployment's settings (bench/configs/gaussian_d4_e7.json) at a CPU
#: size: neval 5e4 gives 12^4 cubes and 6 chunks of 16,384 lanes, which
#: four shards of 2 chunks cover.
CONFIG = {"integrand": "gaussian", "args": {"dim": 4, "mu": 0.5, "sigma": 0.01},
          "neval": 50_000, "ninc": 1024, "alpha": 0.5, "beta": 0.75,
          "max_it": 20, "skip": 2, "max_cubes": 2 ** 18, "chunk": 16_384,
          "dtype": "float32", "rtol": 2e-3}
BACKENDS = ("ref", "pallas-fused")
KEY = 0


def vegas_config(backend, mesh, stop=True):
    c = CONFIG
    return VegasConfig(
        neval=c["neval"], max_it=c["max_it"], skip=c["skip"], ninc=c["ninc"],
        alpha=c["alpha"], beta=c["beta"], max_cubes=c["max_cubes"],
        chunk=c["chunk"], dtype=c["dtype"],
        execution=ExecutionConfig(
            backend=backend, mesh=mesh,
            interpret=True if backend != "ref" else None,
            stop=StopPolicy(rtol=c["rtol"]) if stop else None))


def gaussian():
    return integrands.make_gaussian(**CONFIG["args"])


def one_run(backend, mesh):
    r = run(gaussian(), vegas_config(backend, mesh), key=jax.random.PRNGKey(KEY))
    return {"n_it": r.n_it_used, "mean": r.mean, "sdev": r.sdev,
            "results": np.asarray(r.state.results[:r.n_it_used]).tolist()}


def reuse_check(mesh):
    """Two sharded runs of one plan: the programs they built and reused, and
    whether each result is bitwise that of a program built afresh for it."""
    ig, cfg = gaussian(), vegas_config("ref", mesh)
    keys = [jax.random.PRNGKey(k) for k in (1, 2)]
    executor._clear_program_cache()
    runs, counted = grew(lambda: [run(ig, cfg, key=k) for k in keys])
    fresh = []
    for k in keys:
        executor._clear_program_cache()
        fresh.append(run(ig, cfg, key=k))
    return {"built": counted.get("program.built", 0),
            "reused": counted.get("program.reused", 0),
            "bitwise": all(np.array_equal(np.asarray(a.state.results),
                                          np.asarray(b.state.results))
                           for a, b in zip(runs, fresh))}


def run_mode():
    mesh = make_local_mesh()
    sz = reference.sizes(CONFIG)
    step = reference.make_iteration(CONFIG, sz)
    (i0, s0, _), = reference.replay(step, sz, jax.random.PRNGKey(KEY),
                                    reference.peak(CONFIG), 1)
    out = {"reference_iter0": [i0, s0],
           "exact": reference.exact_value(CONFIG)}
    for backend in BACKENDS:
        m, one = one_run(backend, mesh), one_run(backend, None)
        res = np.asarray(m["results"])
        m["combined_f64"] = reference.combine(res[:, 0], res[:, 1],
                                              CONFIG["skip"])
        out[backend] = {"mesh": m, "one_device": one}
    out["reuse"] = reuse_check(mesh)
    return out


def grew(fn):
    before = obs.counts()
    res = fn()
    return res, {k: v - before.get(k, 0) for k, v in obs.counts().items()}


def obs_mode():
    mesh = make_local_mesh()
    ig = gaussian()
    cfg = vegas_config("ref", mesh)
    rc = make_plan(ig, cfg).cfg
    single, counted = grew(lambda: run(ig, cfg, key=jax.random.PRNGKey(1)))
    fam = make_gaussian_family(np.array([0.4, 0.6]), dim=4, sigma=0.01)
    batched, counted_b = grew(
        lambda: run_batch(fam, cfg, key=jax.random.PRNGKey(2)))

    plan = make_plan(ig, dataclasses.replace(cfg, execution=dataclasses.replace(
        cfg.execution, stop=None)))
    state = core.init_state(ig, plan.cfg, jax.random.PRNGKey(0))
    text = jax.jit(lambda s: core.run_loop(
        s, ig, plan.cfg, 0, fill_fn=_plan_fill_fn(plan))).lower(
            state).compile().as_text()
    all_reduces = [line for line in text.splitlines()
                   if " all-reduce(" in line or " all-reduce-start(" in line]
    return {"dim": rc.dim, "ninc": rc.ninc, "n_cubes": rc.n_cubes,
            "n_cap": rc.n_cap, "chunk": rc.chunk,
            "single": {"n_it": single.n_it_used, "counted": counted},
            "batched": {"b": 2, "n_it_max": int(batched.n_it_used.max()),
                        "counted": counted_b},
            "all_reduces": all_reduces}


if __name__ == "__main__":
    assert jax.device_count() == 4, jax.device_count()
    mode = sys.argv[1]
    out = {"run": run_mode, "obs": obs_mode}[mode]()
    print(json.dumps(out))
