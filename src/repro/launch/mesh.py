"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import (see dryrun.py's first two lines).

Mesh construction goes through :mod:`repro.core.compat`.
"""

from __future__ import annotations

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh for in-process tests (1 device by default)."""
    return compat.make_mesh((data, model), ("data", "model"))
