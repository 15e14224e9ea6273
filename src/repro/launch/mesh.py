"""Mesh construction.  Functions, not module-level constants, so importing
this module never touches jax device state (dry-run sets the 512-device host
platform before first jax init; everything else sees 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple, axes: tuple):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    ``with_sharding_constraint`` stays a hint (``make_mesh`` defaults to
    ``Explicit`` axes, where a constraint is an assertion)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_smoke_mesh():
    """Degenerate 1x1 mesh: lets the sharded step functions run on 1 CPU."""
    return auto_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes the global batch shards over (pod outermost when present)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
