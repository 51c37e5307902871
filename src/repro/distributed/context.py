"""Ambient mesh context.

Model code stays mesh-agnostic: layers that need explicit SPMD (the MoE
expert-parallel island) look the active mesh up here.  The launcher /
dry-run sets it; unit tests run with no mesh (single-device dense fallback).
"""
from __future__ import annotations

import contextlib
from typing import Iterator

from jax.sharding import Mesh

_ACTIVE: list[Mesh | None] = [None]

# Logical -> physical axis mapping (see distributed/sharding.py).
BATCH_AXES = ("pod", "data")  # batch / fsdp axes present in the mesh
MODEL_AXIS = "model"


def current_mesh() -> Mesh | None:
    return _ACTIVE[0]


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None) -> Iterator[None]:
    prev = _ACTIVE[0]
    _ACTIVE[0] = mesh
    try:
        yield
    finally:
        _ACTIVE[0] = prev


def batch_axes(mesh: Mesh | None = None) -> tuple[str, ...]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def model_axis_size(mesh: Mesh | None = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[MODEL_AXIS]
