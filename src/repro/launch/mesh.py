"""Production mesh definition.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests see
the real single CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per chip, 1 axis)
HBM_PER_CHIP = 16 * 2**30       # 16 GiB
