"""Every cell of BENCHMARK.json resolves to files of its own, and every
name and unit keeps to the characters the benchmark's contract allows."""

import json
import re

import pytest

import run as harness

BENCH_JSON = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]
CELLS = BENCH_JSON["workloads"]


def test_top_level_keys():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH_JSON["run_seconds"] <= 51
    assert BENCH_JSON["command"][1] == "bench/run.py"
    assert all(not p.startswith("/") and ".." not in p
               for p in BENCH_JSON["paths"])


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_resolves(cell):
    bench, got, config, traffic, limits = harness.load_cell(cell["name"])
    assert got is not None and limits
    assert (harness.BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for m in harness.metrics_of(bench, cell, True):
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    e2e = {m["name"] for m in harness.metrics_of(bench, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(bench, cell, True)


@pytest.mark.parametrize("cfg", BENCH_JSON["configs"],
                         ids=[c["name"] for c in BENCH_JSON["configs"]])
def test_config_file(cfg):
    path = harness.ROOT / cfg["file"]
    assert path.is_file() and path.parts[-3:-1] == ("bench", "configs")
    assert path.stem == cfg["name"]
    data = json.loads(path.read_text())
    assert data["reduced"] == cfg["reduced"]
    assert data["source"] == cfg["source"]
    assert any(c["config"] == cfg["name"] for c in CELLS)


def test_names_and_units():
    names = [m["name"] for m in METRICS] + [c["name"] for c in CELLS] + \
        [c["name"] for c in BENCH_JSON["configs"]] + \
        [c["traffic"] for c in CELLS]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


def test_metric_entries():
    e2e = {m["name"] for m in BENCH_JSON["end_to_end"]}
    layers = {}
    for m in BENCH_JSON["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH_JSON["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
        for w in m.get("workloads", []):
            cell = next(c for c in CELLS if c["name"] == w)
            bench, *_ = harness.load_cell(cell["name"])
            assert m["moves"] in {x["name"] for x in
                                  harness.metrics_of(bench, cell, False)}
    assert layers
