"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run entry
point must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e-class hardware constants used across roofline accounting
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_BYTES = 16 * 2 ** 30      # per chip


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode: the model stack pins
    shardings with ``with_sharding_constraint``, which refuses the
    Explicit axes ``jax.make_mesh`` makes by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for host-device tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= prod(shape))."""
    return auto_mesh(shape, axes)
