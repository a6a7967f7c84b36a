"""Production mesh definition (DESIGN.md §5).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count at first backend init — dryrun.py must
set XLA_FLAGS before any jax call).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ('data', 'model'); 2 pods adds a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data",)):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = jax.device_count()
    if shape is None:
        shape = (n,)
    return _make_mesh(shape, axes)


def mesh_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
