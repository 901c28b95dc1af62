"""Production mesh builders (TPU v5e pods).

A function, not a module constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes.  The model, exchange and
    serving code shard by logical-axis rules and sharding constraints
    (``repro.dist``), i.e. the compiler propagates layouts; jax's
    default Explicit axes would instead type every eager slice and
    gather of a sharded array, which that code does not annotate."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests and
    the launch/train.py CPU-SPMD path."""
    if model < 1 or n_devices % model != 0:
        raise ValueError(
            f"model axis {model} must divide the device count "
            f"{n_devices}")
    data = n_devices // model
    return make_mesh((data, model), ("data", "model"))
