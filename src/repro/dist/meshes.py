"""Mesh normalization shared by the sharded drivers."""
from __future__ import annotations

import jax


def auto_axes(mesh: jax.sharding.Mesh) -> jax.sharding.Mesh:
    """The same devices and axis names with automatic axes.

    `jax.make_mesh` gives explicit axes, whose sharding-in-types follows
    every array into the traced program: the sweep's dense algebra then
    fails to trace on a TPU (the SVD behind ``pinv`` rejects a sharded
    operand), and callers' jits would need a mesh context. The sharded
    code here only needs `shard_map`'s manual axis.
    """
    return jax.sharding.Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
