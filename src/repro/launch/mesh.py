"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state (required: the dry-run sets
XLA_FLAGS before any jax init; tests must see 1 device)."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """The one place meshes are built.  Axes are `Auto`: the model code
    places values with `with_sharding_constraint` (parallel/axes.py),
    which only accepts Auto axes — `jax.make_mesh` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# v5e-class hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link
