"""Production mesh construction.

Single pod = a 16x16 TPU v5e pod slice (256 chips); multi-pod adds a leading
"pod" axis over DCN. Defined as functions so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import"
        )
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes), devices=devices)
