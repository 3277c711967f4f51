"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import
and carves both meshes out of the 512 placeholder devices; on real hardware
the same call maps onto the actual TPU topology.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — run "
            f"under launch/dryrun.py (it forces 512 host devices) or on a pod")
    # more devices than needed (e.g. 512 forced, single-pod 256 mesh): carve
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes, axis_types=(AxisType.Auto,) * len(axes))


def smoke_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    n = data * model
    devices = jax.devices()[:n]
    arr = np.asarray(devices).reshape((data, model))
    return Mesh(arr, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
