"""Production meshes. Functions only — importing this module never touches
jax device state (required: smoke tests must see 1 device)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A device mesh with Auto axes.

    The model code shards with GSPMD constraints (``shardlib.shard``),
    which need Auto axes; ``jax.make_mesh`` defaults to Explicit ones.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods.

    Axes: (pod,) data, model — `pod` is the slow inter-pod (DCN/optical)
    axis, `data` the FSDP/batch axis, `model` the TP/EP axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI-scale sharding tests (host devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
