"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


import numpy as np


def compat_make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {shape} mesh, have {len(devices)}; "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return compat_make_mesh(shape, axes, devices=devices)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU tests/examples."""
    return compat_make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (per chip) for the roofline model
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link (~ per-direction)
HBM_BYTES = 16 * 1024**3        # 16 GiB
