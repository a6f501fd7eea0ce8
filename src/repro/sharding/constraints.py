"""Logical-axis sharding constraints that degrade to no-ops off-mesh.

Model code annotates activations with *logical* axis names; the mapping to
physical mesh axes lives here so the same model runs (a) un-meshed in CPU
tests, (b) under the single-pod (data, model) mesh and (c) under the
multi-pod (pod, data, model) mesh without edits.

Logical names:
  "data"   -> batch-like dims      -> ("pod","data") if pod axis else "data"
  "model"  -> TP dims              -> "model"
  "heads"  -> attention head dims  -> "model" when divisible, else replicated
  "kv"     -> kv head dims         -> "model" when divisible, else replicated
  "expert" -> MoE expert dim       -> "model"
  None     -> replicated
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

__all__ = [
    "maybe_constrain",
    "logical_to_spec",
    "axis_size",
    "suspend_data_axis",
    "override_data_axes",
]

# When the trainer vmaps the model over the worker dim (spmd_axis_name pins
# it to some mesh axes), inner "data" annotations must not also claim those
# axes.  suspend_data_axis(axes) removes exactly those axes from "data"
# resolution for the enclosed trace (default: all batch-like axes).
_SUSPENDED: frozenset = frozenset()
_DATA_OVERRIDE = None  # e.g. ("model",) under zero3 batch sharding


class override_data_axes:
    """Route logical "data" onto different physical axes (zero3: batch dims
    shard over "model" because params hold no TP there)."""

    def __init__(self, axes):
        self._axes = tuple(axes)

    def __enter__(self):
        global _DATA_OVERRIDE
        self._prev = _DATA_OVERRIDE
        _DATA_OVERRIDE = self._axes
        return self

    def __exit__(self, *exc):
        global _DATA_OVERRIDE
        _DATA_OVERRIDE = self._prev
        return False


class suspend_data_axis:
    def __init__(self, axes=("pod", "data")):
        self._axes = frozenset(axes)

    def __enter__(self):
        global _SUSPENDED
        self._prev = _SUSPENDED
        _SUSPENDED = _SUSPENDED | self._axes
        return self

    def __exit__(self, *exc):
        global _SUSPENDED
        _SUSPENDED = self._prev
        return False


def _mesh():
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty or not m.axis_names:
        return None
    return m


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _resolve(mesh, logical: Optional[str], dim_size: int):
    if logical is None:
        return None
    if logical == "data":
        pool = _DATA_OVERRIDE if _DATA_OVERRIDE is not None else ("pod", "data")
        axes = tuple(
            a for a in pool
            if a in mesh.axis_names and a not in _SUSPENDED
        )
        if not axes:
            return None
        total = 1
        for a in axes:
            total *= axis_size(mesh, a)
        if dim_size % total != 0:
            return None
        return axes if len(axes) > 1 else axes[0]
    if logical in ("model", "expert"):
        if "model" not in mesh.axis_names or dim_size % axis_size(mesh, "model"):
            return None
        return "model"
    if logical in ("heads", "kv"):
        if "model" not in mesh.axis_names or dim_size % axis_size(mesh, "model"):
            return None  # indivisible head counts stay replicated
        return "model"
    raise ValueError(f"unknown logical axis {logical!r}")


def logical_to_spec(mesh, logical_axes, shape) -> P:
    """Resolve logical axes; earlier dims win on physical-axis conflicts
    (zero3 routes "data" onto "model", so a later "model" dim replicates)."""
    used: set = set()
    out = []
    for ax, s in zip(logical_axes, shape):
        r = _resolve(mesh, ax, s)
        flat = (r,) if isinstance(r, str) else tuple(r or ())
        if any(a in used for a in flat):
            r = None
            flat = ()
        used.update(flat)
        out.append(r)
    return P(*out)


def maybe_constrain(x, *logical_axes):
    """with_sharding_constraint with logical axes; no-op without a mesh."""
    mesh = _mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"got {len(logical_axes)} axes for rank-{x.ndim} value"
        )
    spec = logical_to_spec(mesh, logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)
