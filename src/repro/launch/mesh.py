"""Production mesh definition (system-prompt contract).

NOTE: functions, not module-level constants — importing this module never
touches jax device state.  The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before importing jax.
"""
from __future__ import annotations

import jax

from repro.launch.platform import PEAKS


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the solver's and the model's
    shardings are propagated, not typed (the default axis type is
    Explicit)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over real local devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return _make_mesh((data, model), ("data", "model"))


# TPU v5e roofline constants (single chip), from launch/platform.PEAKS
PEAK_FLOPS_BF16 = PEAKS["TPU v5 lite"]["peak_flops"]     # FLOP/s
HBM_BW = PEAKS["TPU v5 lite"]["mem_bw"]                  # bytes/s
ICI_BW = 50e9                   # bytes/s effective per link
