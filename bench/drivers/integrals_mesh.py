"""Driver ``integrals_mesh``: the ``integrals`` driver with the fill's chunk
axis divided over the devices of one host.

Integrals run one at a time, back to back, through ``repro.core.run``, each
to the configuration's rtol, keyed and timed as in ``integrals``; the
configuration's ``mesh`` (``chips``, ``axes``) goes into the
``ExecutionConfig``.  The window also carries the difference of every
program counter (``repro.obs``) across it: ``fill.lanes`` and, where the
program counts it, ``mesh.psum_bytes``.

The check is that of ``integrals``: every integral of the window replayed
with ``bench/reference.py`` on one device.  The replays are spread over the
mesh's devices, integral ``i`` on device ``i mod chips``, one thread a
device, so that they run side by side.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import reference

_spec = importlib.util.spec_from_file_location(
    "bench_integrals", Path(__file__).with_name("integrals.py"))
integrals = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(integrals)


class Driver(integrals.Driver):
    def __init__(self, *, config: dict, traffic: dict, limits: dict,
                 seed: int):
        super().__init__(config=config, traffic=traffic, limits=limits,
                         seed=seed)
        from repro.launch.mesh import make_mesh
        m = config["mesh"]
        self.mesh = make_mesh((m["chips"],), tuple(m["axes"]))
        self.cfg = self.cfg.with_execution(
            dataclasses.replace(self.cfg.execution, mesh=self.mesh))

    def window(self, seconds: float) -> dict:
        from repro import obs
        before = obs.counts()
        w = super().window(seconds)
        w.update({k: v - before.get(k, 0) for k, v in obs.counts().items()})
        return w

    def numbers(self, candidate=None) -> dict:
        """As ``integrals.Driver.numbers``, with the replays spread over the
        mesh's devices."""
        cfg, jax = self.config, self.jax
        done = self.done if candidate is None else candidate
        sz = reference.sizes(cfg)
        step = reference.make_iteration(cfg, sz)
        mu = reference.peak(cfg)
        devices = list(self.mesh.devices.flat)

        def replay_on(j):
            with jax.default_device(devices[j]):
                return [reference.replay(
                    step, sz, jax.random.fold_in(self.base, d["i"]), mu,
                    min(self.replayed, d["n_it"]))
                    for d in done[j::len(devices)]]

        with ThreadPoolExecutor(len(devices)) as pool:
            per_device = list(pool.map(replay_on, range(len(devices))))
        replays = [per_device[k % len(devices)][k // len(devices)]
                   for k in range(len(done))]

        got = {}
        for k in range(self.replayed):
            got[f"iter{k}_rel"], got[f"iter{k}_compared"] = 0.0, 0
        got["combine_rel"] = 0.0
        for d, rep in zip(done, replays):
            for k, (i_k, s_k, margin) in enumerate(rep):
                if margin <= integrals.FLIP_MARGIN:
                    continue
                p_k, q_k = d["results"][k]
                got[f"iter{k}_rel"] = max(got[f"iter{k}_rel"],
                                          integrals._rel(p_k, i_k),
                                          integrals._rel(q_k, s_k))
                got[f"iter{k}_compared"] += 1
            m, s = reference.combine(d["results"][:d["n_it"], 0],
                                     d["results"][:d["n_it"], 1], cfg["skip"])
            got["combine_rel"] = max(got["combine_rel"],
                                     integrals._rel(d["mean"], m),
                                     integrals._rel(d["sdev"], s))
        return got
