"""Production mesh construction.

A function (not a module constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any
device initialization.

Every mesh is built with explicit Auto ``axis_types``.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_test_mesh",
    "abstract_mesh",
    "available_devices",
]


def available_devices() -> int:
    """Visible device count (forced-host devices included) — the mesh
    width benchmarks and tests hand to the ``devices=`` knob of the
    distributed peeling supervisor. Launch-layer only: core code takes
    an explicit integer (or resolves ``"auto"`` itself) so it never
    imports this module."""
    return len(jax.devices())


def _axis_type_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_test_mesh(shape=(1,), axes=("data",)):
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def abstract_mesh(shape, axes):
    """Device-free mesh for mesh-shape-only rule resolution."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))
