"""Production mesh definitions (TPU v5e pods).

Single pod = 256 chips as (16, 16) ('data', 'model'); multi-pod = 2 pods =
512 chips as (2, 16, 16) ('pod', 'data', 'model').  Defined as FUNCTIONS so
importing this module never touches jax device state (device count is locked
at first jax init — dryrun.py sets XLA_FLAGS before importing anything).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} (dryrun.py "
            f"does this automatically)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh() -> Mesh:
    """1-device mesh for smoke tests / examples on CPU."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
