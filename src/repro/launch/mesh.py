"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked on first jax init)."""
from __future__ import annotations

from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips (DP across pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU tests (requires host-platform device override)."""
    return make_mesh((data, model), ("data", "model"))
