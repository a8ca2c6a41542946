"""Subprocess helper of ``test_faults_mesh.py``: one whole run of the
``gaussian_d4_e7.shard4`` cell (warm-up, window, check) at a CPU size on 4
forced host devices, with an optional fault planted in the sharded fill.

    python bench/tests/_mesh_cell.py <fault>
    python bench/tests/_mesh_cell.py control

Prints ``run.py``'s result (or, for ``control``, the readings of
``bench/control.py`` for one seed, both sides) as the last line of
standard output."""

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.append(str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from repro.launch import env as launch_env  # noqa: E402

launch_env.set_host_device_count(4)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run as harness  # noqa: E402
from repro.engine import backends  # noqa: E402

CELL = "gaussian_d4_e7.shard4"
SMALL = {"neval": 50_000, "rtol": 2e-3}


def plant(fault: str) -> None:
    """Wrap the per-shard Kahan fill that ``sharding.make_local_fill``
    binds: its ``(partial, compensation)`` pair goes into the psums."""
    bind = backends.bind_fill

    def faulty_bind(rcfg, **kw):
        fill = bind(rcfg, **kw)
        if not kw.get("return_comp"):
            return fill

        def shard_fill(edges, n_h, key, integrand, *, start_chunk, n_chunks):
            if fault == "one_range":
                start_chunk = 0 * start_chunk
            part, comp = fill(edges, n_h, key, integrand,
                              start_chunk=start_chunk, n_chunks=n_chunks)
            if fault == "shard_left_out":
                gone = start_chunk == 0     # the first shard: the peak's
                part, comp = (jax.tree.map(
                    lambda x: jnp.where(gone, jnp.zeros_like(x), x), t)
                    for t in (part, comp))
            if fault == "compensation_added":
                comp = jax.tree.map(jnp.negative, comp)
            return part, comp
        return shard_fill

    backends.bind_fill = faulty_bind


def control_readings() -> dict:
    import control
    return {r["side"]: r for r in control.readings(
        CELL, [2**33 + 3], 1.0, "both", config_override=SMALL,
        check_device=lambda jax, chips: None)}


def main(fault: str) -> dict:
    if fault == "control":
        return control_readings()
    load = harness.load_cell

    def small(name, *a, **k):
        bench, cell, config, traffic, limits = load(name, *a, **k)
        return bench, cell, dict(config, **SMALL), traffic, limits

    harness.load_cell = small
    if fault != "none":
        plant(fault)
    args = argparse.Namespace(workload=CELL, seed=2**33 + 5, seconds=0.5,
                              trace=0, keep_trace=None)
    return harness.run_cell(args, check_device=lambda jax, chips: None)


if __name__ == "__main__":
    assert jax.device_count() == 4, jax.device_count()
    print(json.dumps(main(sys.argv[1])))
