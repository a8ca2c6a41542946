"""The program's own measurement (`repro.obs`): the host spans of a run, the
device scopes of the loop, the lanes and psum counters, the psum scope and
the fill kernel's name."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.batch import run_batch
from repro.batch.family import make_gaussian_family
from repro.core import VegasConfig, integrands, run
from repro.core import integrator as core
from repro.engine import ExecutionConfig, StopPolicy, make_plan
from repro.engine.executor import make_single_program
from repro.kernels import vegas_fill as vk

SPANS = ("repro.plan", "repro.init", "repro.program", "repro.wait",
         "repro.finish")
SCOPES = ("vegas.cube_ids", "vegas.estimate", "vegas.adapt_nh",
          "vegas.adapt_edges", "vegas.stop")


def small_cfg(**stop):
    return VegasConfig(neval=2000, max_it=5, skip=1, ninc=32, chunk=512,
                       execution=ExecutionConfig(
                           backend="ref", stop=StopPolicy(**stop) if stop
                           else None))


def test_run_spans_nest_on_one_host_thread(tmp_path):
    from jax.profiler import ProfileData
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    cfg = small_cfg(rtol=1e-3)
    run(ig, cfg, key=jax.random.PRNGKey(0))       # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        run(ig, cfg, key=jax.random.PRNGKey(1))
    pb = max(Path(tmp_path).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    threads = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events if e.name.startswith("repro.")]
               for plane in ProfileData.from_file(str(pb)).planes
               for line in plane.lines]
    spans = next(t for t in threads if any(n == "repro.run" for n, _, _ in t))
    (r0, r1), = [(s, e) for n, s, e in spans if n == "repro.run"]
    inside = sorted((s, n) for n, s, e in spans
                    if n != "repro.run" and r0 <= s and e <= r1)
    assert [n for _, n in inside] == list(SPANS)


def test_loop_program_names_its_scopes():
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    plan = make_plan(ig, small_cfg(rtol=1e-3))
    state = core.init_state(ig, plan.cfg, jax.random.PRNGKey(0))
    text = make_single_program(plan).lower(state).as_text(debug_info=True)
    assert all(s in text for s in SCOPES), [s for s in SCOPES
                                            if s not in text]


def test_lanes_count_the_iterations_run():
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    cfg = small_cfg(rtol=0.05)
    n_cap = make_plan(ig, cfg).cfg.n_cap
    before = obs.counts().get("fill.lanes", 0)
    r = run(ig, cfg, key=jax.random.PRNGKey(3))
    assert 0 < r.n_it_used < cfg.max_it      # the stop policy cut the loop
    assert obs.counts()["fill.lanes"] - before == r.n_it_used * n_cap


@pytest.mark.parametrize("stop", [{}, {"rtol": 0.02}], ids=["fixed", "stop"])
def test_lanes_of_a_batched_family(stop):
    """The vmapped loop runs every scenario until the last one stops."""
    fam = make_gaussian_family(np.array([0.3, 0.5, 0.7]), dim=2)
    cfg = small_cfg(**stop)
    n_cap = make_plan(fam, cfg).cfg.n_cap
    before = obs.counts().get("fill.lanes", 0)
    res = run_batch(fam, cfg, key=jax.random.PRNGKey(5))
    grew = obs.counts()["fill.lanes"] - before
    assert grew == 3 * int(res.n_it_used.max()) * n_cap


@pytest.fixture(scope="module")
def mesh_obs(mesh_worker):
    """Counters and compiled all-reduces of runs on a 4-device mesh."""
    return mesh_worker("obs")


@pytest.mark.parametrize("run_kind", ["single", "batched"])
def test_psum_bytes_of_a_mesh_run(mesh_obs, run_kind):
    """Each fill all-reduces its partial FillResult and the partial's
    compensation: 2 x (2 d ninc + 2 n_cubes) float32 values a device."""
    m = mesh_obs
    got = m[run_kind]
    fills = (got["n_it"] if run_kind == "single"
             else got["b"] * got["n_it_max"])
    per_fill = 2 * (2 * m["dim"] * m["ninc"] + 2 * m["n_cubes"]) * 4
    assert got["counted"]["mesh.psum_bytes"] == fills * per_fill
    # The lanes are those the 4 shards run: equal static chunk ranges
    # over every chunk of n_cap, the last range padded with dead chunks.
    per_shard = -(-(m["n_cap"] // m["chunk"]) // 4)
    assert got["counted"]["fill.lanes"] == fills * 4 * per_shard * m["chunk"]
    assert 4 * per_shard * m["chunk"] > m["n_cap"]


def test_no_psum_bytes_on_one_device():
    ig = integrands.make_gaussian(dim=2, sigma=0.1)
    before = obs.counts().get("mesh.psum_bytes", 0)
    run(ig, small_cfg(rtol=0.05), key=jax.random.PRNGKey(3))
    assert obs.counts().get("mesh.psum_bytes", 0) == before


def test_psum_scope_names_the_all_reduces(mesh_obs):
    ops = mesh_obs["all_reduces"]
    assert ops
    assert all('vegas.psum/psum"' in op for op in ops), ops


def test_fused_kernel_is_named():
    d, ninc, chunk, tile = 2, 16, 64, 32

    def fill(key_bits, cube, e, w, u):
        return vk.vegas_fill_fused(
            key_bits, cube, e, w, nstrat=2, n_cubes=4,
            integrand=lambda x: jnp.sum(x, axis=-1), tile=tile,
            interpret=True, u=u)

    args = (jnp.zeros((1, 2), jnp.uint32), jnp.zeros((chunk, 1), jnp.int32),
            jnp.zeros((d, ninc)), jnp.ones((d, ninc)), jnp.zeros((chunk, d)))
    text = jax.jit(fill).lower(*args).as_text(debug_info=True)
    assert "vegas_fill_fused/" in text


def test_counter_adds_under_threads():
    import threading
    before = obs.counts().get("test.threads", 0)

    def add():
        for _ in range(2000):
            obs.count("test.threads", 1)

    workers = [threading.Thread(target=add) for _ in range(8)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=30)
    assert not any(w.is_alive() for w in workers)
    assert obs.counts()["test.threads"] - before == 8 * 2000
