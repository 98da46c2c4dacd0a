"""Production meshes.

Functions (not module-level constants) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (v5e pod).  Multi-pod: 2x16x16 = 512
chips; the leading "pod" axis crosses DCN — batch (and gradient all-reduce)
shards over it, model sharding never does.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
