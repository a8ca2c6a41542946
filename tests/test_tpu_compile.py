"""Mosaic compile rehearsals: the fused fill compiled for a described TPU v5e
(no chip attached) at the sizes the chip runs use — ninc 1024, chunk 16384,
neval 1e7, ``interpret=False`` so the kernel is lowered by Mosaic, not
interpreted.  A compile that the chip's compiler would refuse (an unaligned
reshape, a primitive Pallas cannot lower, a tile over the scoped VMEM limit)
fails here first.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.batch.family import make_gaussian_family
from repro.core import VegasConfig
from repro.core import integrands as igs
from repro.core import integrator as core
from repro.engine import (ExecutionConfig, PlanError, PrecisionPolicy,
                          StopPolicy, make_plan)
from repro.engine import sharding as sharding_mod
from repro.engine.executor import _plan_fill_fn
from repro.kernels import ops as kops
from repro.launch.integrate import INTEGRANDS

NEVAL = 10_000_000
NINC = 1024
CHUNK = 16_384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fill_args(rc, sharding, batch=()):
    return (jax.ShapeDtypeStruct(batch + (rc.dim, rc.ninc + 1), jnp.float32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct(batch + (rc.n_cubes,), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct(batch + (2,), jnp.uint32, sharding=sharding))


def _compiled_fill(integrand, rc):
    def fill(edges, n_h, key):
        return kops.fill(edges, n_h, key, integrand, nstrat=rc.nstrat,
                         n_cap=rc.n_cap, chunk=rc.chunk, interpret=False)
    return fill


@pytest.mark.parametrize("name", sorted(INTEGRANDS))
def test_fused_fill_compiles_for_v5e(one_chip, name):
    ig = INTEGRANDS[name]()
    rc = VegasConfig(neval=NEVAL, ninc=NINC, chunk=CHUNK).resolve(ig.dim)
    compiled = jax.jit(_compiled_fill(ig, rc)).lower(
        *_fill_args(rc, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("ig_fn,tile", [
    (lambda: igs.make_gaussian(dim=4, mu=0.5, sigma=0.01), 256),
    (lambda: igs.make_roos_arnold(dim=10), 128),
], ids=["gaussian_d4", "roos_arnold_d10"])
def test_fused_fill_compiles_for_v5e_at_autotuned_tile(one_chip, ig_fn, tile):
    """The benchmark's shapes (1e6 evaluations, ``max_cubes`` 2^18, chunk
    16384): the tile the VMEM model picks for bf16 one-hots fits Mosaic's
    scoped VMEM."""
    ig = ig_fn()
    rc = VegasConfig(neval=1_000_000, ninc=NINC, chunk=CHUNK,
                     max_cubes=2 ** 18).resolve(ig.dim)
    assert kops.autotune_tile(
        rc.chunk, ig.dim, rc.ninc, rc.n_cubes,
        row_bytes=kops.eval_row_bytes(ig, ig.dim)) == tile
    compiled = jax.jit(_compiled_fill(ig, rc)).lower(
        *_fill_args(rc, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vmapped_family_fill_compiles_for_v5e(one_chip):
    fam = make_gaussian_family(np.linspace(0.2, 0.8, 64))
    rc = VegasConfig(neval=NEVAL, ninc=NINC, chunk=CHUNK).resolve(fam.dim)

    def fill(params, edges, n_h, key):
        one = lambda p, e, nh, k: _compiled_fill(fam.bind(p), rc)(e, nh, k)
        return jax.vmap(one)(params, edges, n_h, key)

    params = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fill).lower(
        params, *_fill_args(rc, one_chip, batch=(64,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_fill_compiles_for_v5e_2x2(topo):
    ig = igs.make_gaussian()
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    cfg = VegasConfig(neval=10 * NEVAL, ninc=NINC, chunk=CHUNK,
                      execution=ExecutionConfig(backend="pallas-fused",
                                                interpret=False))
    rc = cfg.resolve(ig.dim)
    fill = sharding_mod.make_sharded_fill(mesh, ("data",), rc)
    compiled = jax.jit(lambda e, nh, k: fill(e, nh, k, ig)).lower(
        *_fill_args(rc, NamedSharding(mesh, P()))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo


def test_sharded_run_compiles_for_v5e_2x2(topo):
    """The whole-run program of the ``gaussian_d4_e7`` deployment, as
    ``execute`` builds it: the stop policy's ``while_loop`` around the
    shard_mapped fill, 4 shards of 160 chunks.  Both psums of an iteration
    (partials and compensations) become all-reduces under ``vegas.psum``."""
    ig = igs.make_gaussian(dim=4, mu=0.5, sigma=0.01)
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    cfg = VegasConfig(neval=NEVAL, ninc=NINC, chunk=CHUNK, max_it=20, skip=2,
                      alpha=0.5, beta=0.75, max_cubes=2 ** 18,
                      execution=ExecutionConfig(
                          backend="pallas-fused", interpret=False, mesh=mesh,
                          stop=StopPolicy(rtol=5e-5)))
    plan = make_plan(ig, cfg)
    rc = plan.cfg
    assert (plan.n_shards, rc.n_cap // rc.chunk) == (4, 639)
    rep = NamedSharding(mesh, P())
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=rep)
    state = core.VegasState(spec((rc.dim, rc.ninc + 1), jnp.float32),
                            spec((rc.n_cubes,), jnp.int32),
                            spec((2,), jnp.uint32), spec((), jnp.int32),
                            spec((rc.max_it, 2), jnp.float32))
    prog = jax.jit(functools.partial(
        core.run_loop, integrand=ig, cfg=rc, start=0,
        fill_fn=_plan_fill_fn(plan), stop=plan.stop), donate_argnums=0)
    compiled = prog.lower(state).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    reduces = [line for line in hlo.splitlines()
               if " all-reduce(" in line or " all-reduce-start(" in line]
    assert reduces and all("vegas.psum/" in r for r in reduces), reduces
    # A device holds at most its shard's temporaries (2,621,440 cube ids are
    # 10.5 MB of int32), never the whole axis's (42 MB).
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def test_widened_accumulation_refused_when_compiled_for_tpu():
    cfg = VegasConfig(neval=NEVAL, ninc=NINC, chunk=CHUNK)
    widened = PrecisionPolicy(accum_dtype="float64")
    for backend in ("pallas", "pallas-fused"):
        compiled = ExecutionConfig(backend=backend, interpret=False,
                                   precision=widened)
        with pytest.raises(PlanError, match="Mosaic lowers no float64") as e:
            make_plan(igs.make_gaussian(), cfg, compiled)
        assert "\n" not in str(e.value)
