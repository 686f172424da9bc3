"""Mesh and shard_map construction with the engine's fixed settings.

Every mesh axis is ``Auto`` (the compiler places what the program leaves
unsharded), and shard_map skips its per-shard replication check: the query
programs return per-device tables whose replication the engine tracks itself.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map"]


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with per-shard replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
