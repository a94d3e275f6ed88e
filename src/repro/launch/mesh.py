"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The single-pod mesh is 16x16 = 256 chips ("data","model");
the multi-pod mesh is 2x16x16 = 512 chips ("pod","data","model").
``make_mesh_for`` generalizes to arbitrary device counts for elastic
re-meshing (see train/elastic.py).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def _make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules place
    parameters and let the compiler (GSPMD) propagate the rest.  JAX's
    default is Explicit axes, under which every traced op must agree on
    sharding types (Pallas interpret mode, for one, does not)."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(num_devices: int, *, model_parallelism: int = 16,
                  pods: int = 1, devices: Optional[Sequence] = None):
    """Largest (pod, data, model) mesh that fits ``num_devices`` devices,
    built from ``devices`` (default: every device of the backend)."""
    model = model_parallelism
    while model > 1 and num_devices % model:
        model //= 2
    data = num_devices // (model * pods)
    if data < 1:
        raise ValueError(
            f"cannot build mesh: {num_devices} devices, model={model}, "
            f"pods={pods}")
    if pods > 1:
        return _make_mesh((pods, data, model), ("pod", "data", "model"),
                          devices)
    return _make_mesh((data, model), ("data", "model"), devices)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def mesh_context(mesh):
    """``jax.set_mesh(mesh)``: the launchers' single mesh-scoping entry
    point."""
    import jax

    return jax.set_mesh(mesh)
