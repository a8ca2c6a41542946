"""The readers of the mesh layer's metrics, on a synthetic context."""

import pytest

import run as harness

MS = harness.load_module(harness.BENCH / "metrics" / "psum.ms_per_iter.py")
GBS = harness.load_module(harness.BENCH / "metrics" / "psum.gb_per_s.py")


def ctx(collective_s=0.05, iterations=100, **window):
    return {"trace": {"collective_s": collective_s, "kernel_s": 16.0},
            "window": dict(iterations=iterations, **window)}


def test_psum_ms_per_iter():
    assert MS.read(ctx()) == pytest.approx(0.5)


@pytest.mark.parametrize("c", [ctx(iterations=0), ctx(collective_s=0.0)],
                         ids=["no_iterations", "no_collectives"])
def test_psum_ms_per_iter_reads_nothing(c):
    assert MS.read(c) is None


def test_psum_gb_per_s():
    c = ctx(**{"mesh.psum_bytes": 100 * 3_813_632})
    assert GBS.read(c) == pytest.approx(100 * 3_813_632 / 0.05 / 1e9)


@pytest.mark.parametrize("c", [ctx(), ctx(collective_s=0.0,
                                          **{"mesh.psum_bytes": 10})],
                         ids=["no_counter", "no_collectives"])
def test_psum_gb_per_s_reads_nothing(c):
    """None where the program keeps no psum counter (a program that
    predates it) or the trace holds no collective."""
    assert GBS.read(c) is None
