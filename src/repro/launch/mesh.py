"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and nothing else should.
"""
from __future__ import annotations

import jax


import numpy as np


def _mesh(shape, axes):
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "BEFORE importing jax")
    devices = np.asarray(devs[:n]).reshape(shape)
    return jax.sharding.Mesh(
        devices, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — smoke tests."""
    return _mesh((data, model), ("data", "model"))


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple:
    """Data-parallel axes: ('pod','data') multi-pod, ('data',) single-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
