"""Production mesh construction (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry run must set XLA_FLAGS before the first jax initialization.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips (v5e-256).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: Optional[int] = None, model_axis: int = 2):
    """Small mesh over whatever devices exist (unit tests)."""
    n = n_devices or len(jax.devices())
    model_axis = min(model_axis, n)
    data_axis = n // model_axis
    return jax.make_mesh((data_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants (per chip) used by the roofline analysis.
V5E_PEAK_FLOPS_BF16 = 197e12       # FLOP/s
V5E_HBM_BW = 819e9                 # B/s
V5E_ICI_LINK_BW = 50e9             # B/s per link (~; see EXPERIMENTS.md)
V5E_HBM_BYTES = 16 * 1024 ** 3     # 16 GiB
