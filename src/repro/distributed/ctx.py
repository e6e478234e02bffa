"""Activation-sharding context.

Models call ``shard_act(x, ...logical axes...)`` at a few key points (residual
stream, MoE dispatch buffers). Outside a mesh context this is a no-op, so
tests/serving on one device are untouched; the dry-run/launchers install the
production mesh here and the constraints materialize as Megatron-SP-style
activation sharding (residuals sharded over the model axis between blocks)
and EP-aligned MoE buffers.

Logical axes: "dp" resolves to ("pod","data") when a pod axis exists, else
("data",); any other string must name a mesh axis. A constraint on a
dimension that does not divide by its axis product silently replicates —
every arch/mesh combination stays compilable.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def activation_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_activation_mesh(mesh: Optional[Mesh]):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev


def _resolve(axis, mesh: Mesh) -> Optional[Tuple[str, ...]]:
    if axis is None:
        return None
    if axis == "dp":
        return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if isinstance(axis, str):
        axis = (axis,)
    return tuple(axis)


def shard_act(x, *axes):
    """with_sharding_constraint(x, P(*axes)) if a mesh is installed and every
    constrained dim divides; otherwise identity."""
    mesh = _MESH
    if mesh is None or not hasattr(x, "ndim") or x.ndim != len(axes):
        return x
    spec = []
    for dim, axis in zip(x.shape, axes):
        names = _resolve(axis, mesh)
        if names is None:
            spec.append(None)
            continue
        names = tuple(a for a in names if a in mesh.axis_names)
        size = 1
        for a in names:
            size *= mesh.shape[a]
        spec.append(names if (size > 1 and dim % size == 0) else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
