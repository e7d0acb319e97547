"""Production meshes.

Importing this module never touches jax device state — meshes are built by
functions only. The dry-run (and ONLY the dry-run) forces 512 host devices
via XLA_FLAGS before any jax import (launch/dryrun.py lines 1-2).

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 2 pods = 512 chips.
Axes: "data" (batch + FSDP), "model" (tensor parallel), "pod" (cross-pod DP).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    return _make_mesh((n // model_parallel, model_parallel), ("data", "model"))


# Hardware constants for the roofline (TPU v5e, per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_LINK_BW = 50e9              # bytes/s per link (~ per-chip usable)
HBM_PER_CHIP = 16 * 2**30       # bytes
