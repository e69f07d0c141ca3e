"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax use;
tests and benches keep their 1-device view).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_axes(mesh) -> tuple:
    """((name, size), ...) in mesh order — the planner's mesh description."""
    return tuple(zip(mesh.axis_names, mesh.devices.shape))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host devices (tests, examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
