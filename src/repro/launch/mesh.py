"""Production meshes: one v5e pod (16x16 = 256 chips) and 2 pods (512).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state — smoke tests keep
seeing 1 CPU device; only dryrun.py forces 512 host platform devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code places activations with sharding constraints
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke runs through the same code path."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW_PER_LINK = 50e9  # B/s (~ per link)
HBM_BYTES = 16 * 1024**3
