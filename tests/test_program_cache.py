"""The executor's cache of single-scenario whole-run programs: a repeated
``core.run`` of one integrand and config traces its program once, the reused
program gives what a fresh build gives, every input the program depends on
keys its own entry, and the cache stays within its bound."""

import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import VegasConfig, integrands, run
from repro.core.integrands import Integrand
from repro.engine import ExecutionConfig, StopPolicy, make_plan
from repro.engine import executor

CFG = VegasConfig(neval=4_000, max_it=4, skip=1, ninc=32, chunk=2048)
COUNTERS = ("program.built", "program.reused")


@pytest.fixture(autouse=True)
def empty_cache():
    executor._clear_program_cache()
    yield
    executor._clear_program_cache()


def counting_gaussian():
    """A gaussian integrand whose Python body counts how often it runs:
    once per trace, since a jitted body runs only while tracing."""
    base = integrands.make_gaussian(dim=2, sigma=0.1)
    traced = {"n": 0}

    def fn(x):
        traced["n"] += 1
        return base.fn(x)
    return dataclasses.replace(base, fn=fn), traced


def grew(fn):
    before = obs.counts()
    out = fn()
    after = obs.counts()
    return out, {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def same(a, b):
    np.testing.assert_array_equal(np.asarray(a.state.results),
                                  np.asarray(b.state.results))
    np.testing.assert_array_equal(np.asarray(a.state.edges),
                                  np.asarray(b.state.edges))
    assert (a.mean, a.sdev, a.n_it_used) == (b.mean, b.sdev, b.n_it_used)


def test_second_run_reuses_the_program():
    ig, traced = counting_gaussian()
    r1, first = grew(lambda: run(ig, CFG, key=jax.random.PRNGKey(1)))
    n_traced = traced["n"]
    assert n_traced > 0
    r2, second = grew(lambda: run(ig, CFG, key=jax.random.PRNGKey(2)))
    assert traced["n"] == n_traced
    assert first == {"program.built": 1, "program.reused": 0}
    assert second == {"program.built": 0, "program.reused": 1}
    assert r1.mean != r2.mean          # the key reached the reused program


@pytest.mark.parametrize("stop", [None, StopPolicy(rtol=0.05)],
                         ids=["fori", "while"])
def test_reused_program_gives_a_fresh_build_bitwise(stop):
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    cfg = CFG.with_execution(ExecutionConfig(stop=stop))
    run(ig, cfg, key=jax.random.PRNGKey(1))
    cached, counted = grew(lambda: run(ig, cfg, key=jax.random.PRNGKey(2)))
    assert counted["program.reused"] == 1
    executor._clear_program_cache()
    fresh, counted = grew(lambda: run(ig, cfg, key=jax.random.PRNGKey(2)))
    assert counted["program.built"] == 1
    same(cached, fresh)


def _variants():
    """Each case: a run made after the base run ``run(ig, CFG)``, which
    must build its own program once and reuse it after."""
    ig, twin = (integrands.make_gaussian(dim=2, sigma=0.1) for _ in range(2))

    def other_neval():
        run(ig, dataclasses.replace(CFG, neval=6_000))

    def other_rtol():
        run(ig, CFG.with_execution(ExecutionConfig(stop=StopPolicy(rtol=0.1))))

    def other_integrand():
        run(twin, CFG)

    def other_backend():
        run(ig, CFG.with_execution(ExecutionConfig(backend="pallas-fused",
                                                   interpret=True)))

    def resumed():
        r = run(ig, dataclasses.replace(CFG, max_it=2))
        executor._clear_program_cache()
        run(ig, CFG)                              # the base, from start 0
        return lambda: run(ig, CFG, state=r.state)   # start 2
    return ig, {"neval": other_neval, "rtol": other_rtol,
                "integrand": other_integrand, "backend": other_backend,
                "resume": resumed}


@pytest.mark.parametrize("case", ["neval", "rtol", "integrand", "backend",
                                  "resume"])
def test_what_the_program_depends_on_keys_its_own_entry(case):
    ig, variants = _variants()
    if case == "resume":
        again = variants[case]()
    else:
        run(ig, CFG)
        again = variants[case]
    _, counted = grew(again)
    assert counted == {"program.built": 1, "program.reused": 0}
    _, counted = grew(again)
    assert counted == {"program.built": 0, "program.reused": 1}


def test_cache_never_holds_more_than_its_bound():
    """Programs are built without being traced, so the bound can be filled
    at its real size; the least recently used entry goes first."""
    bound = executor._PROGRAM_CACHE_SIZE
    plans = [make_plan(integrands.make_gaussian(dim=2, sigma=0.1), CFG)
             for _ in range(bound + 3)]
    for p in plans[:bound]:
        executor._cached_single_program(p, 0, False)
    assert len(executor._programs) == bound
    _, counted = grew(lambda: executor._cached_single_program(
        plans[0], 0, False))
    assert counted["program.reused"] == 1          # plans[0] now newest
    for p in plans[bound:]:
        executor._cached_single_program(p, 0, False)
        assert len(executor._programs) <= bound
    assert len(executor._programs) == bound
    _, counted = grew(lambda: executor._cached_single_program(
        plans[0], 0, False))
    assert counted["program.reused"] == 1
    _, counted = grew(lambda: executor._cached_single_program(
        plans[1], 0, False))
    assert counted["program.built"] == 1           # evicted


def test_a_state_resumed_twice_survives_donation():
    """The reused program donates its input; the caller's state is not it.
    (A host view of an array pins its buffer against donation on the CPU,
    so the expected values come from a twin run, not from ``saved``.)"""
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    first = dataclasses.replace(CFG, max_it=2)
    saved = run(ig, first, key=jax.random.PRNGKey(4)).state
    kept = jax.tree.map(np.asarray,
                        run(ig, first, key=jax.random.PRNGKey(4)).state)
    a = run(ig, CFG, state=saved)
    b = run(ig, CFG, state=saved)
    same(a, b)
    for got, want in zip(jax.tree.leaves(saved), jax.tree.leaves(kept)):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_paths_outside_the_cache_build_per_call(tmp_path):
    """A caller's fill_fn, the checkpoint host loop and a key that does not
    hash each build their program for the call alone."""
    from repro.engine import backends
    from repro.engine.config import CheckpointPolicy
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    rc = make_plan(ig, CFG).cfg
    fill = backends.bind_fill(rc, backend="ref")
    listed = Integrand("listed", 2, ig.fn, [0.0, 0.0], [1.0, 1.0])
    ckpt = CFG.with_execution(ExecutionConfig(
        checkpoint=CheckpointPolicy(directory=str(tmp_path))))
    for call in (lambda: run(ig, CFG, fill_fn=fill),
                 lambda: run(ig, ckpt),
                 lambda: run(listed, CFG)):
        for _ in range(2):
            _, counted = grew(call)
            assert counted == {"program.built": 0, "program.reused": 0}
    assert len(executor._programs) == 0

