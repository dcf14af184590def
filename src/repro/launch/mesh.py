"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests see the real single CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e-class hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw_per_link": 50e9,       # bytes/s per link (~3 links/chip in 3D)
    "hbm_bytes": 16e9,
}
