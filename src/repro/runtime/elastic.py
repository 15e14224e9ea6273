"""Elastic scaling: recover onto a degraded (or grown) mesh.

When nodes are lost, continuing on an arbitrary survivor count fragments the
sharding; the policy here is **power-of-two shrink**: pick the largest
(data, model) mesh with data' <= data a power of two and model unchanged
(model-parallel groups are co-located; losing one kills its slice anyway, so
elasticity operates on the data axis).  The checkpoint is restored onto the
new mesh (checkpoint/restore takes a shardings tree), the data pipeline
re-shards deterministically (any host can produce any shard), and the global
batch is preserved by raising per-replica microbatching.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.launch.mesh import auto_mesh
from repro.observability import events


def largest_pow2_leq(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


@dataclass(frozen=True)
class ElasticPlan:
    old_shape: tuple
    new_shape: tuple
    axis_names: tuple
    grad_accum_factor: int   # microbatch multiplier to preserve global batch


def plan_remesh(old_shape: tuple, axis_names: tuple,
                devices_available: int) -> ElasticPlan:
    """Shrink the data axis to fit ``devices_available`` devices."""
    model = old_shape[-1]
    lead = old_shape[:-2]            # ('pod',) or ()
    lead_n = 1
    for d in lead:
        lead_n *= d
    assert devices_available >= model, "cannot preserve model-parallel groups"
    max_data = devices_available // (model * lead_n)
    new_data = largest_pow2_leq(max_data)
    assert new_data >= 1
    old_data = old_shape[-2]
    accum = max(1, old_data // new_data)
    plan = ElasticPlan(old_shape, lead + (new_data, model), axis_names, accum)
    if events.enabled():
        events.emit("elastic.remesh", old_shape=list(old_shape),
                    new_shape=list(plan.new_shape),
                    devices_available=devices_available,
                    grad_accum_factor=accum)
    return plan


def build_mesh(plan: ElasticPlan):
    return auto_mesh(plan.new_shape, plan.axis_names)
