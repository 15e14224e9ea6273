"""A kind of layer's share of its roofline on the chip.

The least time of a layer is the larger of its operations over the chip's
peak rate and its bytes over the peak HBM bandwidth, both from
``bench/peaks.json``; the work is the configuration's own (``work`` in its
model module), whatever kernel ran it.  The share is the least time of every
layer of the kind, summed per forward and times the forwards traced, over the
device time of the ops that implement them.
"""
from __future__ import annotations


def least_time(layer: dict, peaks) -> tuple[float, str]:
    """Seconds the layer needs at the chip's peaks, and which peak bounds it."""
    compute, memory = layer["flops"] / peaks.flops, layer["bytes"] / peaks.hbm
    return (compute, "flops") if compute >= memory else (memory, "bytes")


def share(ctx, kind: str) -> float | None:
    """Percent of the roofline that the ops of ``kind`` reached, or ``None``
    when the trace holds no time for them or the work has no such layer."""
    layers = [layer for layer in ctx.work if layer["kind"] == kind]
    device_s = ctx.trace.category_s.get(kind, 0.0)
    if not layers or device_s <= 0 or ctx.requests <= 0:
        return None
    least = sum(least_time(layer, ctx.peaks)[0] for layer in layers)
    return 100.0 * least * ctx.requests / device_s
