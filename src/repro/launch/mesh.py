"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (jax locks the device count on first init).
"""
from __future__ import annotations

from typing import Sequence

import jax

from repro.common.config import MeshConfig


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips of v5e) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_config(mc: MeshConfig):
    return make_mesh(mc.shape, mc.axes)


def make_debug_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small host-device mesh for tests (requires
    --xla_force_host_platform_device_count to already be set)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
