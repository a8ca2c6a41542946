"""The reduction from trace events to device-time numbers."""

import pytest

import run as harness

T = harness.load_module(harness.BENCH / "trace.py")
SLICE = harness.BENCH / "tests" / "data" / "gaussian_d4.single.slice.json.gz"
KERNEL = '%k = f32[8] custom-call(f32[8] %a), custom_call_target="tpu_custom_call"'


def synthetic():
    """A ``while`` around two operations, a kernel inside it, an
    all-reduce, and host spans; times in ns over a window [0, 200)."""
    ops = [["%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100],
           ["%fusion.2 = f32[8] fusion(f32[8] %x), kind=kLoop", 0, 30],
           [KERNEL, 40, 30],
           ["%all-reduce.3 = f32[8] all-reduce(f32[8] %y), to_apply=%s",
            80, 10],
           ["%copy.4 = f32[8] copy(f32[8] %z)", 150, 10]]
    host = [["bench.window", 0, 200], ["bench.integral", 0, 120],
            ["lower_sharding_computation", 85, 70], ["bench.fetch", 120, 80]]
    return {"devices": {"/device:TPU:0": ops,
                        "/device:CUSTOM:Megascale Trace": []},
            "host": {"/host:CPU/python3": host}}


def test_split_and_busy_union():
    r = T.reduce(synthetic())
    assert r["kernel_s"] == pytest.approx(30e-9)
    assert r["collective_s"] == pytest.approx(10e-9)
    assert r["xla_s"] == pytest.approx(40e-9)       # the while is no leaf
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["window_s"] == pytest.approx(200e-9)


def test_idle_gaps_go_to_the_host_span():
    gaps = dict(T.reduce(synthetic())["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench.integral": 20e-9,
                                  "bench.fetch/lower_sharding_computation":
                                      60e-9,
                                  "bench.fetch": 40e-9})


def test_no_tpu_plane_is_an_error():
    ev = synthetic()
    ev["devices"] = {"/device:CUSTOM:Megascale Trace": []}
    with pytest.raises(ValueError):
        T.reduce(ev)


def test_recorded_slice():
    """Three fill-kernel calls of a gaussian_d4 window recorded on a TPU
    v5e, with the operations between them."""
    ev = T.read_saved(SLICE)
    r = T.reduce(ev)
    ops = ev["devices"]["/device:TPU:0"]
    kernels = [d for n, s, d in ops if T.KERNEL_MARK in n]
    assert len(kernels) == 3
    assert r["kernel_s"] == pytest.approx(sum(kernels) / 1e9)
    assert r["kernel_s"] + r["xla_s"] + r["collective_s"] == pytest.approx(
        r["busy_s"], rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert all(k.startswith("bench.integral")
               for k, _ in r["breakdown"]["idle_gaps"])
    top = r["breakdown"]["device_ops"][0][0]
    assert top.startswith("%fusion.93") and len(top) <= T.LABEL
