"""The check fails a run whose timed path is broken underneath.

Each test drives a whole run of an integral cell (warm-up, window, check)
at a small size on the CPU, past the harness's look for a chip, with one
fault planted in the program, and sees ``correct`` come out false; the
unbroken run comes out true.  The first faults break what every run
does; the last ones break only the adapted path (the map, the allocation
and the cube ids once the allocation is no longer uniform), which the
first, uniform, iteration never reaches.
"""

import argparse
import dataclasses

import pytest

import run as harness

SMALL = {"neval": 50_000, "rtol": 2e-3}
CELLS = ("gaussian_d4.single", "roos_arnold_d10.single")
#: The cells whose check replays the first adapted iteration.
ADAPTED = tuple(c for c in CELLS if "iter1_rel" in harness.load_cell(c)[4])


def small_run(workload: str, monkeypatch) -> dict:
    load = harness.load_cell

    def small(name, *a, **k):
        bench, cell, config, traffic, limits = load(name, *a, **k)
        return bench, cell, dict(config, **SMALL), traffic, limits

    monkeypatch.setattr(harness, "load_cell", small)
    args = argparse.Namespace(workload=workload, seed=2**33 + 5, seconds=0.5,
                              trace=0, keep_trace=None)
    return harness.run_cell(args, check_device=lambda jax, chips: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, monkeypatch):
    out = small_run(workload, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged(workload, monkeypatch):
    from repro.core import integrator
    monkeypatch.setattr(integrator, "run_loop",
                        lambda state, *a, **k: state)
    assert not small_run(workload, monkeypatch)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out(workload, monkeypatch):
    """The integrand sees the first half of every batch of points and its
    values stand for the second half too: a mean over half the samples."""
    from repro.core import integrands
    import jax.numpy as jnp

    def halve(make):
        def build(**kw):
            ig = make(**kw)

            def fn(x):
                half = ig.fn(x[: x.shape[0] // 2])
                return jnp.concatenate([half, half])[: x.shape[0]]
            return dataclasses.replace(ig, fn=fn)
        return build

    for name in ("make_gaussian", "make_roos_arnold"):
        monkeypatch.setattr(integrands, name, halve(getattr(integrands, name)))
    assert not small_run(workload, monkeypatch)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered(workload, monkeypatch):
    """The estimate is altered where it is produced, by one part in 1e3."""
    from repro.engine import executor
    single = executor._execute_single

    def altered(*a, **k):
        r = single(*a, **k)
        return dataclasses.replace(r, mean=r.mean * (1 + 1e-3))

    monkeypatch.setattr(executor, "_execute_single", altered)
    assert not small_run(workload, monkeypatch)["correct"]


def _patch(monkeypatch, module, name, wrap):
    monkeypatch.setattr(module, name, wrap(getattr(module, name)))


def adapted_fault_caught(out: dict) -> bool:
    """The run is not correct, and only the replay of the first adapted
    iteration says so: the first iteration does not reach the fault."""
    checks = out["checks"]
    first = checks["iter0_rel"]["value"] <= checks["iter0_rel"]["limit"]
    return (not out["correct"] and first
            and checks["iter1_rel"]["value"] > checks["iter1_rel"]["limit"])


@pytest.mark.parametrize("workload", ADAPTED)
def test_map_histogram_zeroed(workload, monkeypatch):
    """The map adapts from a histogram of zeros: it stays uniform."""
    from repro.core import map as vmap_
    _patch(monkeypatch, vmap_, "adapt_edges",
           lambda adapt: lambda edges, sums, counts, alpha:
           adapt(edges, 0.0 * sums, counts, alpha))
    assert adapted_fault_caught(small_run(workload, monkeypatch))


@pytest.mark.parametrize("workload", ADAPTED)
def test_map_histogram_shifted(workload, monkeypatch):
    """The histogram reaches the map a quarter of the intervals off."""
    import jax.numpy as jnp
    from repro.core import map as vmap_
    _patch(monkeypatch, vmap_, "adapt_edges",
           lambda adapt: lambda edges, sums, counts, alpha:
           adapt(edges, jnp.roll(sums, sums.shape[1] // 4, axis=1), counts,
                 alpha))
    assert adapted_fault_caught(small_run(workload, monkeypatch))


@pytest.mark.parametrize("workload", ADAPTED)
def test_allocation_permuted(workload, monkeypatch):
    """Each cube gets the evaluations another cube asked for."""
    from repro.core import strat
    _patch(monkeypatch, strat, "adapt_nh",
           lambda adapt: lambda *a, **k: adapt(*a, **k)[::-1])
    assert adapted_fault_caught(small_run(workload, monkeypatch))


@pytest.mark.parametrize("workload", ADAPTED)
def test_cube_ids_uniform(workload, monkeypatch):
    """Cube ids of the evaluation axis from the uniform allocation's
    formula, whatever the allocation is."""
    import jax.numpy as jnp
    from repro.core import strat

    def uniform_ids(n_h, start, length):
        per = jnp.maximum(jnp.sum(n_h) // n_h.shape[0], 1)
        e = start + jnp.arange(length, dtype=per.dtype)
        return jnp.minimum(e // per, n_h.shape[0]).astype(jnp.int32)

    monkeypatch.setattr(strat, "cubes_for_slice", uniform_ids)
    assert adapted_fault_caught(small_run(workload, monkeypatch))
