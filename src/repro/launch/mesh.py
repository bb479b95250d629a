"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (tests / CPU runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS = 197e12      # bf16 FLOP/s
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s per link
