"""Production meshes. (pod, data, model) = (2, 16, 16) multi-pod; (16, 16)
single-pod — 256 chips/pod of TPU v5e, 512 total.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from repro.parallel.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_dev_mesh(n_devices: int = 1, model: int = 1):
    """Small mesh for tests on local devices."""
    data = n_devices // model
    return auto_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    import math
    return math.prod(mesh.devices.shape)
