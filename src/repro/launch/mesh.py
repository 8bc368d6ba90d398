"""Production meshes (assignment): single pod (16, 16) = 256 chips with axes
(data, model); multi-pod (2, 16, 16) = 512 chips with axes (pod, data,
model).  A FUNCTION, not a module constant — importing this module never
touches jax device state.

The *runtime* mesh — the one the trainer / rollout / serving stack actually
executes on — is configured with ``repro.distributed.mesh.MeshConfig``
(re-exported here), which raises when the host cannot fit the axes
(DESIGN.md §8).
"""
from __future__ import annotations

from repro.distributed.mesh import MeshConfig, make_mesh  # noqa: F401


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(model: int = 2, data: int = 2):
    """Tiny mesh for unit tests (requires >= model*data host devices)."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
