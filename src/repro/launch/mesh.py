"""Production meshes.

Single-pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism over the DCN (gradient all-reduce only),
"data" is in-pod FSDP/batch, "model" is TP/EP.  Functions, not module
constants, so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the default Explicit axes reject
    the ``with_sharding_constraint`` calls the model code makes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over ("pod"+"data" when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_devices(mesh) -> int:
    import numpy as np
    return int(np.prod(list(mesh.shape.values())))
