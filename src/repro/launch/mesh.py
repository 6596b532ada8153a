"""Production meshes.  A FUNCTION (not module-level constant) so importing
never touches jax device state.  Single pod: (data=16, model=16) = 256 chips
of TPU v5e; multi-pod adds a leading 'pod' axis (2 pods = 512 chips)."""
from __future__ import annotations

from repro.parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for subprocess multi-device tests (8 host devices)."""
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline):
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-chip usable for collectives, 1 link)
