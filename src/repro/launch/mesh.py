"""Mesh construction.

FUNCTIONS, not module constants: importing this module must never touch jax
device state (multi-device tests set the host-device-count override before
any jax initialization).  All mesh construction in this repo (tests,
examples, benches) goes through :func:`make_mesh`."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis Auto (the sharded fill partitions
    its chunk axis by hand inside ``shard_map``)."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_local_mesh():
    """Whatever devices exist locally, as a 1D data mesh (tests/examples).
    This is the mesh ``dist.sharded_fill.make_sharded_fill`` expects for
    single-host multi-device runs (DESIGN.md §5)."""
    return make_mesh((jax.device_count(),), ("data",))
