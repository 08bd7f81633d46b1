"""Production mesh construction (spec'd in the multi-pod dry-run contract).

A FUNCTION, not a module constant — importing this module never touches jax
device state. The 512 placeholder host devices are installed by dryrun.py
(and ONLY dryrun.py) via XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small test mesh over however many (host) devices exist."""
    return _make_mesh((data, model), ("data", "model"))


#: TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
