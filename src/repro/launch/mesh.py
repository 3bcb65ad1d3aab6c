"""Production meshes (assignment MULTI-POD DRY-RUN §1).

A FUNCTION, not a module constant — importing this module never touches jax
device state.  Single pod: (16, 16) ("data", "model") = 256 chips.
Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips across 2 pods.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4, *, multi_pod: bool = False):
    """Small mesh for CI-sized sharding tests (requires host-device override)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))


def dp_total(mesh) -> int:
    n = mesh.shape.get("data", 1)
    if "pod" in mesh.shape:
        n *= mesh.shape["pod"]
    return n
