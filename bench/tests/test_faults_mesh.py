"""The check of the four-device cell fails a run whose sharded fill is
broken, and fails the control (``bench/control.py``).

Each test drives a whole run of ``gaussian_d4_e7.shard4`` (warm-up, window,
check) at a CPU size on 4 forced host devices, in a subprocess
(``_mesh_cell.py``), with one fault planted in the per-shard fill whose
partials the psums add up, and sees ``correct`` come out false; the
unbroken run comes out true.

Not among the faults: the Kahan compensation added instead of subtracted.
It moves a sum by its rounding error, a few ulps, which no check whose
limit clears the sound runs' readings can see (at this size the readings
are those of the sound run to the last digit).
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

HELPER = Path(__file__).with_name("_mesh_cell.py")
FAULTS = {
    "shard_left_out": "the first shard's partial (the peak's chunks) is "
                      "zeroed before the psums",
    "one_range": "every shard fills the first shard's chunk range: the "
                 "traced start offset is lost",
}


def mesh_run(fault: str) -> dict:
    out = subprocess.run([sys.executable, str(HELPER), fault],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct():
    out = mesh_run("none")
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_mesh_fault_is_caught(fault):
    out = mesh_run(fault)
    assert not out["correct"], (FAULTS[fault], out["checks"])


def test_control_fails_program_passes():
    """As ``test_control.py`` holds for the one-chip cells: the program's
    readings pass the cell's limits, the bfloat16 reference's do not."""
    got = mesh_run("control")
    limits = json.loads((HELPER.parents[1] / "limits"
                         / "gaussian_d4_e7.shard4.json").read_text())
    assert all(got["program"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got
