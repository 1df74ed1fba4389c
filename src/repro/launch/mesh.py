"""Production mesh builders (deliverable e).

Functions, not module-level constants, so importing this module never
touches jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before importing jax;
tests and benches see the real single CPU device.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

from repro.parallel.sharding import MeshAxes

# TPU v5e hardware constants used by the roofline pass (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s per link (~ one direction)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axes(mesh) -> MeshAxes:
    """Logical roles for a production mesh: every non-"model" axis is a
    dp/fsdp axis; "model" is the TP/EP axis."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    return MeshAxes(dp=dp, tp="model")


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for multi-device unit tests (subprocess with forced
    host device count)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
