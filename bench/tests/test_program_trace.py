"""The reading of the program's own spans, scopes and counter from a trace
(``bench/program_trace.py``), beside the harness's reduction, which it
leaves as it was."""

import argparse

import pytest

import run as harness
from test_trace import synthetic

P = harness.load_module(harness.BENCH / "program_trace.py")
T = P.T
DATA = harness.BENCH / "tests" / "data"
SLICE = DATA / "gaussian_d4.single.slice.json.gz"
SCOPED = DATA / "gaussian_d4.single.scoped.slice.json.gz"


def scoped():
    """``synthetic`` with a scope path on each operation and the program's
    spans on the host thread."""
    ev = synthetic()
    paths = ["jit(run_loop)/while",
             "jit(run_loop)/while/body/while/body/vegas.cube_ids/"
             "searchsorted/while/body/gather",
             "jit(run_loop)/while/body/while/body/vegas_fill_fused",
             "jit(run_loop)/while/body/vmap(vegas.stop)/vegas.estimate/psum",
             ""]
    ev["devices"]["/device:TPU:0"] = [
        op + [p] for op, p in zip(ev["devices"]["/device:TPU:0"], paths)]
    ev["host"]["/host:CPU/python3"] += [
        ["repro.run", 0, 118], ["repro.program", 2, 58],
        ["repro.wait", 70, 40]]
    return ev


def test_recorded_slice_numbers_are_pinned():
    """What the harness's reduction gives the first recorded slice, which
    has neither scopes nor the program's spans."""
    r = T.reduce(T.read_saved(SLICE))
    assert r["kernel_s"] == pytest.approx(0.003001322, rel=1e-12)
    assert r["collective_s"] == 0.0
    assert r["xla_s"] == pytest.approx(0.004347185, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.007348507, rel=1e-12)
    assert r["window_s"] == pytest.approx(0.007360225, rel=1e-12)
    ops = [(k.split(" = ")[0], v) for k, v in r["breakdown"]["device_ops"]]
    assert ops == [(k, pytest.approx(v, rel=1e-9)) for k, v in [
        ("%fusion.93", 4.247219e-3), ("%closed_call.16", 3.001322e-3),
        ("%reduce-window.55", 5.5911e-5), ("%copy.37", 1.8419e-5),
        ("%pad_bitcast_fusion.6", 5.258e-6), ("%reduce-window.56", 4.715e-6),
        ("%select_select_fusion.5", 1.927e-6), ("%copy.35", 1.6e-6),
        ("%copy.36", 1.599e-6), ("%select_select_fusion.4", 1.503e-6)]]
    assert r["breakdown"]["idle_gaps"] == [
        ["bench.integral/np.asarray(jax.Array)", pytest.approx(1.1718e-5)]]
    p = P.reduce(T.read_saved(SLICE))
    assert p["by_scope"] == {"": pytest.approx(r["busy_s"])}
    assert p["idle_by_span"] == {
        "": pytest.approx(r["window_s"] - r["busy_s"])}


def test_device_time_by_outermost_scope():
    r = P.reduce(scoped())
    assert r["by_scope"] == pytest.approx({"vegas.cube_ids": 30e-9,
                                           "vegas.stop": 10e-9, "": 40e-9})
    two = scoped()
    two["devices"]["/device:TPU:1"] = [
        [n, s, d / 2, p] for n, s, d, p in two["devices"]["/device:TPU:0"]]
    assert P.reduce(two, n_devices=2)["by_scope"] == pytest.approx(
        {"vegas.cube_ids": 22.5e-9, "vegas.stop": 7.5e-9, "": 30e-9})


def test_idle_by_innermost_program_span():
    ev = scoped()
    r = P.reduce(ev)
    # Gaps [30, 40), [70, 80), [90, 150), [160, 200): midpoints 35 in
    # repro.program, 75 in repro.wait, 120 and 180 past repro.run.
    assert r["idle_by_span"] == pytest.approx(
        {"repro.program": 10e-9, "repro.wait": 10e-9, "": 100e-9})
    base = T.reduce(P.plain(ev))
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_three_field_events_have_no_scope():
    """Events saved without scopes reduce to one unnamed scope, and the
    harness reduces the device operations of the scoped events' plain copy
    as it reduces those that never had scopes."""
    r = P.reduce(synthetic())
    assert r["by_scope"] == pytest.approx({"": 80e-9})
    assert r["idle_by_span"] == pytest.approx({"": 120e-9})
    a, b = T.reduce(P.plain(scoped())), T.reduce(synthetic())
    for k in ("kernel_s", "collective_s", "xla_s", "busy_s", "window_s"):
        assert a[k] == b[k]
    assert a["breakdown"]["device_ops"] == b["breakdown"]["device_ops"]


def test_no_tpu_plane_is_an_error():
    ev = scoped()
    ev["devices"] = {"/device:CUSTOM:Megascale Trace": []}
    with pytest.raises(ValueError):
        P.reduce(ev)


def test_op_names_from_the_trace_metadata(tmp_path):
    """The scope path of each instruction, read from the module HLO that a
    profiler trace keeps (here of a CPU program: the reading is the
    chip's)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ids(n_h, e):
        with jax.named_scope("vegas.cube_ids"):
            return jnp.searchsorted(jnp.cumsum(n_h), e)

    args = jnp.arange(1, 9), jnp.arange(20)
    ids(*args).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        ids(*args).block_until_ready()
    raw = max(tmp_path.rglob("*.xplane.pb"),
              key=lambda p: p.stat().st_mtime).read_bytes()
    names = P.read_op_names(raw)
    module = next(m for m in names if m.startswith("jit_ids("))
    found = {i: p for i, p in names[module].items()
             if p.startswith("jit(ids)/vegas.cube_ids/")}
    assert found and all(P.scope_of(p) == "vegas.cube_ids"
                         for p in found.values())


def test_recorded_scoped_slice():
    """Two iterations of a gaussian_d4 window recorded on a TPU v5e, with
    the program's scopes and spans: the operations under
    ``vegas.cube_ids`` are the ``searchsorted`` fusion that tops the
    device time, the kernel is named and under no scope, and every scope
    of the loop is there."""
    ev = T.read_saved(SCOPED)
    base = T.reduce(P.plain(ev))
    r = P.reduce(ev)
    ops = P.leaves(ev["devices"]["/device:TPU:0"])
    cube = [o for o in ops if P.scope_of(o[3]) == "vegas.cube_ids"]
    assert cube and all("/vegas.cube_ids/jit(searchsorted)/" in o[3]
                        for o in cube)
    top, top_s = base["breakdown"]["device_ops"][0]
    assert top.startswith("%fusion.93 ")
    fusion = sum(o[2] for o in cube if o[0].startswith("%fusion.93 ")) / 1e9
    assert fusion == pytest.approx(top_s)
    assert fusion > 0.99 * r["by_scope"]["vegas.cube_ids"]
    kernels = [o for o in ops if T.KERNEL_MARK in o[0]]
    assert len(kernels) == 2 * 90          # two iterations of 90 chunks
    assert all(o[0].startswith("%vegas_fill_fused") and not P.scope_of(o[3])
               for o in kernels)
    assert set(r["by_scope"]) == {"", "vegas.cube_ids", "vegas.estimate",
                                  "vegas.adapt_nh", "vegas.adapt_edges",
                                  "vegas.stop"}
    assert sum(r["by_scope"].values()) == pytest.approx(
        base["kernel_s"] + base["xla_s"] + base["collective_s"])
    # Inside the loop the host waits on the device.
    assert set(r["idle_by_span"]) == {"repro.wait"}


def test_readings_of_the_spans_scopes_and_counter():
    ev = scoped()
    out = P.readings(T.reduce(P.plain(ev)), P.reduce(ev),
                     {"iterations": 2, "integrals": 4, "fill.lanes": 600})
    assert out == pytest.approx({
        "strat.cube_ids.ms_per_iter": 15e-6, "adapt.ms_per_iter": 5e-6,
        "host.program_idle_ms.integral": 2.5e-6,
        "host.run_idle_ms.integral": 2.5e-6,
        "fill.lanes_per_s.integral": 600 / 30e-9})


def test_readings_left_out_without_the_program_marks():
    """A program without spans, scopes or counters gives nothing to read,
    and each reading is left out rather than raise."""
    ev = synthetic()
    assert P.readings(T.reduce(ev), P.reduce(ev),
                      {"iterations": 9, "integrals": 1}) == {}


def test_window_counts_the_program_lanes(monkeypatch):
    """A small gaussian_d4 window on the CPU: the window carries the
    difference of the program's ``fill.lanes`` counter across it, the
    padded lanes of every iteration its integrals ran, and its integrals
    check out.  The CPU trace has no TPU plane, so a synthetic one stands
    in for what the chip would record."""
    from repro.core import VegasConfig
    from repro.core import integrands
    small = {"neval": 50_000, "rtol": 2e-3}
    load = harness.load_cell

    def load_small(name, *a, **k):
        bench, cell, config, traffic, limits = load(name, *a, **k)
        return bench, cell, dict(config, **small), traffic, limits

    monkeypatch.setattr(harness, "load_cell", load_small)
    monkeypatch.setattr(P, "load_events", lambda logdir: scoped())
    args = argparse.Namespace(workload="gaussian_d4.single", seed=2**33 + 5,
                              seconds=0.5)
    out = P.measure(args, check_device=lambda jax, chips: None)
    assert out["correct"]
    config = harness.load_cell("gaussian_d4.single")[2]
    n_cap = VegasConfig(neval=config["neval"], max_cubes=config["max_cubes"],
                        chunk=config["chunk"]).resolve(
        integrands.make_gaussian(**config["args"]).dim).n_cap
    window = out["window"]
    assert window["iterations"] > 0
    assert window["fill.lanes"] == window["iterations"] * n_cap
    assert out["readings"]["fill.lanes_per_s.integral"] == pytest.approx(
        window["fill.lanes"] / 30e-9)
