"""Mesh construction.

Production:  (data=16, model=16)          — 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)   — 512 chips across 2 pods
Local:       (data=#devices/model, model)  — whatever this host holds

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run launcher sets XLA_FLAGS before any jax import to fake
the production device count; the trainer CLI builds the local mesh from
the devices present.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_debug_mesh",
    "make_local_mesh",
    "worker_axes",
    "num_workers",
]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small explicit mesh (device count permitting)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def make_local_mesh(model: int = 1):
    """(data, model) mesh over every device this process sees: ``model``
    devices per tensor-parallel group, the rest on the worker axis."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(f"model={model} does not divide {n} devices")
    return _make_mesh((n // model, model), ("data", "model"))


def worker_axes(mesh) -> tuple:
    """Mesh axes that enumerate Byz-VR-MARINA-PP workers/clients."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_workers(mesh) -> int:
    n = 1
    for a in worker_axes(mesh):
        n *= mesh.shape[a]
    return n
