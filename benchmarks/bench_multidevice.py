"""Paper Fig. 5 / Table 8: multi-device scaling of the fill phase.

Runs the sharded fill on 1/2/4/8 forced host devices in subprocesses.
HONESTY NOTE: this container has ONE physical core, so host "devices" are
time-sliced and wall-clock speedup is structurally ~1x here; the table
reports the two quantities that ARE meaningful in the dry-run setting:
  * per-device eval count (work drops 1/n — the paper's C1 balance), and
  * psum'd accumulator bytes (constant in n_eval — the Amdahl argument that
    gave cuVegas 0.85 efficiency at 8 GPUs, Table 8).
Real-TPU wall-clock scaling is a hardware measurement, not reproducible here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_WORKER = r"""
import os, sys, json, time
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from repro.core import integrator as I
from repro.core.integrands import make_ridge
from repro.dist import sharded_fill as SF
from repro.launch.mesh import make_mesh

ig = make_ridge(dim=4, n_peaks=200)
cfg = I.VegasConfig(neval=200_000, max_it=4, ninc=512, chunk=8192).resolve(ig.dim)
mesh = make_mesh((n,), ("data",))
fill = SF.make_sharded_fill(mesh, ("data",), cfg)
st = I.init_state(ig, cfg, jax.random.PRNGKey(0))
key = jax.random.fold_in(st.key, 0)
r = jax.block_until_ready(fill(st.edges, st.n_h, key, ig))   # compile
t0 = time.perf_counter()
for _ in range(3):
    r = jax.block_until_ready(fill(st.edges, st.n_h, key, ig))
dt = (time.perf_counter() - t0) / 3
chunks = cfg.n_cap // cfg.chunk
per_dev = -(-chunks // n) * cfg.chunk
psum_bytes = (cfg.ninc * ig.dim * 2 + cfg.n_cubes * 2) * 4
print(json.dumps(dict(n=n, wall=dt, per_dev_evals=per_dev,
                      psum_bytes=psum_bytes, mean=float(r.cube_s1.sum()))))
"""


def run(fast=True):
    import jax
    if jax.default_backend() == "tpu":
        # Its children force host CPU devices, and this process holds the
        # chip: their rows would say nothing about the chip.
        raise RuntimeError("table8 runs forced host CPU devices only; on "
                           "a TPU run the sharded path on the chip itself")
    devs = [1, 2, 4, 8]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    base = None
    for n in devs:
        out = subprocess.run([sys.executable, "-c", _WORKER, str(n)],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        if out.returncode != 0:
            emit(f"table8/gpus={n}", 0.0, f"ERROR {out.stderr[-200:]}")
            continue
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        base = base or rec
        emit(f"table8/devices={n}", rec["wall"],
             f"per_dev_evals={rec['per_dev_evals']} "
             f"psum_bytes={rec['psum_bytes']} "
             f"work_reduction={base['per_dev_evals']/rec['per_dev_evals']:.2f}x")


if __name__ == "__main__":
    run()
