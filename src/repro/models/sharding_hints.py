"""Activation sharding anchors (GSPMD constraint hints).

The global scheme (DESIGN.md §4): activations shard **by tokens** — batch
over ('pod','data'), sequence over 'model' — and weights are storage-sharded
over both axes and all-gathered on use (ZeRO-3/FSDP via GSPMD propagation).
Token sharding works for *every* assigned arch (head counts 9/15/28/40 don't
divide a 16-way model axis, so head-TP cannot be the universal rule), keeps
all GEMM compute perfectly partitioned, and makes attention sequence-parallel
(each 'model' shard computes its query-block slice against gathered KV).

These helpers read the ambient abstract mesh and no-op when there is none
(CPU smoke tests) or when an axis does not divide the dimension.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _mesh_axes():
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return None
    return mesh


def _batch_axes(mesh):
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def constrain_tokens(x, batch: int | None = None, seq_axis: int = 1):
    """x: (B, T, ...) -> P(batch_axes, 'model', None...) when divisible."""
    mesh = _mesh_axes()
    if mesh is None:
        return x
    ba = _batch_axes(mesh)
    spec = [None] * x.ndim
    if ba and x.shape[0] % _axis_size(mesh, ba) == 0:
        spec[0] = ba if len(ba) > 1 else ba[0]
    if ("model" in mesh.axis_names and x.ndim > seq_axis
            and x.shape[seq_axis] % mesh.shape["model"] == 0
            and x.shape[seq_axis] >= mesh.shape["model"]):
        spec[seq_axis] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain(x, spec_axes: tuple):
    """Generic anchor; axes not present in the mesh or non-divisible -> None."""
    mesh = _mesh_axes()
    if mesh is None:
        return x
    spec = []
    for dim, ax in enumerate(spec_axes):
        if ax is None:
            spec.append(None)
            continue
        axs = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                    if a in mesh.axis_names)
        if not axs or x.shape[dim] % _axis_size(mesh, axs) != 0:
            spec.append(None)
            continue
        spec.append(axs if len(axs) > 1 else axs[0])
    return jax.lax.with_sharding_constraint(x, P(*spec))


BATCH = ("pod", "data")   # canonical batch sharding axes (filtered to mesh)
