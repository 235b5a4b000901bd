"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import os
import sys


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated shardings)."""
    import jax

    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    set before jax init — see `ensure_cpu_devices`)."""
    return _make_mesh(shape, axes)


def make_serving_mesh(mesh_shape):
    """Mesh for `ServeConfig.mesh_shape` (DESIGN §12): last axis is
    "model" (tensor parallelism), leading axes ("data",) or
    ("pod", "data")."""
    shape = tuple(mesh_shape)
    axes = ("pod", "data", "model")[-len(shape):]
    return _make_mesh(shape, axes)


def ensure_cpu_devices(n: int) -> bool:
    """Ask XLA's host platform for >= n devices (CPU test meshes,
    DESIGN §12). Must run BEFORE jax initializes; returns False (and
    changes nothing) when jax is already imported or the flag is already
    set — callers on real accelerators are unaffected (the flag only
    applies to the host platform)."""
    flag = "--xla_force_host_platform_device_count"
    current = os.environ.get("XLA_FLAGS", "")
    if "jax" in sys.modules or flag in current:
        return False
    os.environ["XLA_FLAGS"] = f"{current} {flag}={n}".strip()
    return True
