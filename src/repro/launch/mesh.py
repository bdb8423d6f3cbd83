"""Production meshes (DESIGN.md §5).

Defined as *functions* so importing this module never touches jax device
state. The dry-run sets ``XLA_FLAGS=--xla_force_host_platform_device_count=
512`` before any jax import; smoke tests and benches see 1 device.

Mesh shapes (TPU v5e pods):
* single-pod: (16, 16) -> ("data", "model")  — 256 chips
* multi-pod:  (2, 16, 16) -> ("pod", "data", "model") — 512 chips
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
