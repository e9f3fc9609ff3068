"""Production meshes.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis is
pure data parallelism over the inter-pod DCN, i.e. exactly the lossy
PS-over-WAN link the paper's LTP targets (DESIGN.md §2).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (device count is locked at first jax init —
the dry-run sets XLA_FLAGS before importing anything else).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types: GSPMD places whatever the
    code does not constrain (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link
