import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

# Tests and benches must see the single real CPU device (multi-device suites
# force extra host devices in subprocesses only, tests/_dist_worker.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# The RNG contract (DESIGN.md C5 / §7) claims bit-exact streams under BOTH
# threefry counter layouts; CI runs the tier-1 suite twice, flipping this
# env var, so neither layout is the untested one.
_partitionable = os.environ.get("REPRO_THREEFRY_PARTITIONABLE", "1") != "0"
jax.config.update("jax_threefry_partitionable", _partitionable)


@pytest.fixture(scope="session")
def mesh_worker():
    """``mesh_worker(mode)``: the JSON object that ``_mesh_worker.py <mode>``
    prints last, from a subprocess with 4 forced host devices, run once a
    session for each mode."""
    worker = Path(__file__).with_name("_mesh_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).parents[1] / "src"), env.get("PYTHONPATH", "")])
    done = {}

    def get(mode):
        if mode not in done:
            out = subprocess.run([sys.executable, str(worker), mode], env=env,
                                 capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, \
                f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
            done[mode] = json.loads(out.stdout.strip().splitlines()[-1])
        return done[mode]
    return get
