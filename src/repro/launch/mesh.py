"""Production mesh definition (required shape, DESIGN.md §4).

A FUNCTION, not a module constant — importing this module never touches
jax device state.  Every axis is ``Auto``: the shard_map engines place
their own data and leave the rest to the partitioner, while ``jax.make_mesh``
defaults to ``Explicit`` axes that make every ambiguous gather an error.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(devices: int = 8):
    """Small mesh for CI-scale shard_map tests (data × model, model=2).

    Degrades to model=1 on odd/single-device hosts so the CLI ``--mesh``
    path stays runnable without forced device counts.
    """
    model = 2 if devices >= 2 and devices % 2 == 0 else 1
    data = devices // model
    return auto_mesh((data, model), ("data", "model"))


# canonical impl lives in the dist layer (repro.dist.sharding.data_axes):
# "the batch/edge-parallel axes of a mesh ('pod' included when present)"
from repro.dist.sharding import data_axes  # noqa: E402,F401
