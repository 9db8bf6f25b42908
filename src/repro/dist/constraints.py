"""Logical sharding hints for model code (DESIGN.md §4).

Model layers annotate activations with *logical* axis names and this module
resolves them against whatever mesh is active (``with jax.set_mesh(mesh)``);
with no active mesh every hint is a no-op, so the same model code runs in
single-device smoke tests and on the production mesh unchanged.

Logical axes:
  * ``"batch"`` — the data-parallel axes (``data``, plus ``pod`` when the
    mesh has one): batch/token dims of activations;
  * ``"tp"``    — the ``model`` axis: feature/vocab/expert dims;
  * ``"full"``  — every mesh axis combined: giant node/edge tables that
    should be flat-sharded over the whole slice (GNN scatter outputs);
  * ``None``    — replicated / no constraint for that dim.

A hint only applies when the dim size is divisible by the resolved axis
size — otherwise that dim silently stays unconstrained (GSPMD would pad,
and padded segment-sums corrupt masked graph reductions).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P


def active_mesh():
    """The mesh installed by ``jax.set_mesh``, else None.

    This is the abstract mesh (axis names and sizes, no devices): inside
    ``jax.jit`` it is the only one JAX exposes, and axis sizes plus a
    ``PartitionSpec`` are all a hint needs.
    """
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _resolve(name, mesh) -> tuple:
    """Logical name -> tuple of mesh axis names present on this mesh."""
    if name is None:
        return ()
    names = _axis_sizes(mesh)
    if name == "tp":
        axes = ("model",)
    elif name == "batch":
        axes = ("pod", "data")
    elif name == "full":
        axes = ("pod", "data", "model")
    else:                                   # explicit mesh axis name
        axes = (name,)
    return tuple(a for a in axes if a in names)


def data_shards() -> int:
    """Number of shards on the data-parallel axes of the active mesh (1 when
    no mesh is active) — used by MoE dispatch for shard-local ranking."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    sizes = _axis_sizes(mesh)
    return int(math.prod(sizes[a] for a in _resolve("batch", mesh)) or 1)


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """``with_sharding_constraint`` with logical names, one per dim of x.

    No-op when no mesh is active, when a named axis is absent from the
    mesh, or when the dim size is not divisible by the axis size.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    sizes = _axis_sizes(mesh)
    spec = []
    for dim, name in enumerate(logical_axes):
        axes = _resolve(name, mesh)
        n = math.prod(sizes[a] for a in axes) if axes else 0
        if axes and n > 0 and dim < x.ndim and x.shape[dim] % n == 0:
            spec.append(axes[0] if len(axes) == 1 else axes)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
