"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the "pod"
axis crosses DCN and carries the data-parallel gradient reduction +
FSDP parameter sharding of the outermost degree.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper (smoke tests, elastic re-meshes).

    Every axis is typed ``Auto``, so GSPMD propagates shardings and
    ``shard_map`` bodies interoperate with the sharding rules.
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
