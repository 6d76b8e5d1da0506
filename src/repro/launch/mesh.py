"""Production mesh definitions (assignment contract).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


class HW:
    """TPU v5e roofline constants (assignment contract)."""

    PEAK_FLOPS_BF16 = 197e12   # per chip
    HBM_BW = 819e9             # bytes/s per chip
    ICI_BW = 50e9              # bytes/s per link
