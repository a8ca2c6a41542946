"""The control (the reference in bfloat16 in the program's place) fails each
cell's check, and the program passes it, at a small size on the CPU."""

import json

import pytest

import control
import run as harness

SMALL = {   # config and traffic overrides, window seconds
    "gaussian_d4.single": ({"neval": 50_000, "rtol": 2e-3}, {}, 1.0),
    "roos_arnold_d10.single": ({"neval": 50_000, "rtol": 2e-3}, {}, 1.0),
}
CELLS = [c["name"] for c in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_program_passes(workload):
    config, traffic, seconds = SMALL[workload]
    limits = harness.load_cell(workload)[4]
    got = {r["side"]: r for r in control.readings(
        workload, [2**33 + 3], seconds, "both", config_override=config,
        traffic_override=traffic, check_device=lambda jax, chips: None)}
    assert all(got["program"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got
