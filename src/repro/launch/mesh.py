"""Production meshes.  Defined as functions (never module-level constants) so
importing this module does not touch jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 v5e chips as ('data','model') = (16,16).
    Multi-pod: 2 pods x 256 chips as ('pod','data','model') = (2,16,16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


# v5e hardware constants (roofline denominators; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
