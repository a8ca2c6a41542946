"""One integral sharded over four devices through ``repro.core.run``: the
``gaussian_d4_e7`` deployment's path at a CPU size, against the benchmark's
plain reference (``bench/reference.py``) and against the same run on one
device.  The runs happen in a subprocess (``_mesh_worker.py``) with 4
forced host devices, so that the forced count never leaks into this
process."""

import numpy as np
import pytest

BACKENDS = ("ref", "pallas-fused")

#: Iteration 0 runs on the uniform map and allocation, so the program and
#: the reference draw the same points into the same cubes; they differ only
#: in float32 summation order (four Kahan partials and a psum against one
#: chunk-sequential scatter-add).
ITER0_RTOL = 2e-5
#: The program's float32 running combination against the float64 one of
#: the iterations it reported: a few ulps.
COMBINE_RTOL = 1e-6
#: Mesh and one device: the same samples, summed in another order, which
#: the fill bounds to about an ulp.  Each adaptation feeds the last bit
#: back into the map, and after five or so the two runs drift apart
#: (1e-3 at the seventh iteration at this size, an allocation flip), as
#: any two summation orders do; the iterations before that are compared.
SAME_RTOL = 2e-5
SAME_ITERS = 3


@pytest.fixture(scope="module")
def runs(mesh_worker):
    return mesh_worker("run")


def rel(a, b):
    return abs(a - b) / abs(b)


@pytest.mark.parametrize("backend", BACKENDS)
def test_iteration0_matches_reference(runs, backend):
    got = runs[backend]["mesh"]["results"][0]
    want = runs["reference_iter0"]
    assert rel(got[0], want[0]) <= ITER0_RTOL, (got, want)
    assert rel(got[1], want[1]) <= ITER0_RTOL, (got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_combination_matches_float64(runs, backend):
    m = runs[backend]["mesh"]
    mean, sdev = m["combined_f64"]
    assert rel(m["mean"], mean) <= COMBINE_RTOL
    assert rel(m["sdev"], sdev) <= COMBINE_RTOL


@pytest.mark.parametrize("backend", BACKENDS)
def test_first_iterations_match_one_device(runs, backend):
    mesh = np.asarray(runs[backend]["mesh"]["results"][:SAME_ITERS])
    one = np.asarray(runs[backend]["one_device"]["results"][:SAME_ITERS])
    np.testing.assert_allclose(mesh, one, rtol=SAME_RTOL, atol=0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_mesh_run_meets_rtol_like_one_device(runs, backend):
    """Both runs stop on the rtol, within an iteration of each other (their
    sdevs drift apart by a few per cent at most), and agree with each other
    and with the closed form to within their standard deviations."""
    mesh, one = runs[backend]["mesh"], runs[backend]["one_device"]
    for r in (mesh, one):
        assert 2 <= r["n_it"] < 20
        assert r["sdev"] <= 2e-3 * abs(r["mean"])
    assert abs(mesh["n_it"] - one["n_it"]) <= 1
    assert abs(mesh["mean"] - one["mean"]) <= 3 * max(mesh["sdev"],
                                                      one["sdev"])
    assert abs(mesh["mean"] - runs["exact"]) <= 4 * mesh["sdev"]


def test_sharded_runs_of_one_plan_reuse_one_program(runs):
    """The second run of a sharded plan reuses the first's program, and
    gives bitwise what a program built afresh gives."""
    assert runs["reuse"] == {"built": 1, "reused": 1, "bitwise": True}
