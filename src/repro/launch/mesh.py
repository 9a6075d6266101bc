"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
``xla_force_host_platform_device_count`` dance.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh over whatever single device is present (CPU smoke)."""
    return _make_mesh((1, 1), ("data", "model"))


def describe(mesh) -> str:
    return (f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} = "
            f"{mesh.devices.size} devices")
