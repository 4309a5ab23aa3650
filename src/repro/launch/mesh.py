"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
initialization. Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis is DCN
data parallelism (see DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh with Auto axis types (tests / small-scale runs)."""
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def host_device_mesh(n_data: int = 1, n_model: int = 1) -> jax.sharding.Mesh:
    """Mesh over however many (possibly fake) devices exist."""
    return make_mesh((n_data, n_model), ("data", "model"))
