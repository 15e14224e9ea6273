"""Device time per named layer, and the gaps inside each forward, from a trace.

The program names its work where it happens: ``carla_conv`` runs each conv
under ``jax.named_scope(<layer>)`` (``conv1``, ``conv3_b0_proj``,
``conv3_b0_1x1a``, ``conv3_b0_3x3``, ``conv3_b0_1x1b``, ...), the strided
route of ``kernels/ops.py`` runs its patches under ``im2col`` and its GEMM
under ``gemm``, ``resnet50_apply`` runs the max pool under ``maxpool`` and the
mean and fc under ``head``, and each Pallas kernel carries its ``pallas_call``
name.  In ``compiled.as_text()`` an ENTRY instruction's ``op_name`` then reads
``jit(<forward>)/conv1/jit(_conv2d_jit)/im2col/gather``: its scope is the
names between the forward's jit and the primitive, the inner jits and the
kernel's own name left out (``conv1/im2col``).

This reduction reads those names and nothing else; it is separate from
:mod:`bench.devtrace`, whose categories it does not change.  Where the
compiled text carries no scope (a program that names nothing, or an
executable loaded from a cache that such a program wrote: JAX's cache key
leaves debug information out), :func:`reduce_scopes` returns ``None``.  An op
the compiler made (no ``op_name``, or an argument's name: layout copies,
async slices) goes with the ops it feeds when they agree on one scope, as in
:func:`bench.devtrace.classify`.

Times are the device's own.  Each ``XLA Modules`` event of a device plane is
one forward, and its ops are the ``XLA Ops`` events that start inside it, so
no host clock is needed.  A forward's gap is its interval less the union of
its ops: time inside a forward in which the device ran nothing.
"""
from __future__ import annotations

import bisect
import collections
from dataclasses import dataclass

from bench import devtrace, roofline

MODULES_LINE = "XLA Modules"
UNSCOPED = ""          # an op of the forward under no named scope
WEIGHT_STATIONARY = "_mm_weight_stationary_kernel"


def scope_of(op: devtrace.HloOp) -> str | None:
    """``layer[/part]`` of an op traced in the program, ``UNSCOPED`` for one
    under no scope, ``None`` for an op the compiler made."""
    if not op.op_name or not op.op_name.startswith("jit("):
        return None
    path = [p for p in op.op_name.split("/")[1:-1]
            if not p.startswith("jit(") and p != op.kernel]
    return "/".join(path)


def scopes(ops: dict[str, devtrace.HloOp]) -> dict[str, str]:
    """The scope of every ENTRY instruction (see the module docstring)."""
    users = collections.defaultdict(list)
    for op in ops.values():
        for o in op.operands:
            users[o].append(op.name)
    out = {n: s for n, op in ops.items() if (s := scope_of(op)) is not None}
    decided = set(out)
    for n in ops:
        if n not in out:
            fed = {out[u] for u in devtrace._walk(n, users, decided.__contains__)}
            out[n] = fed.pop() if len(fed) == 1 else UNSCOPED
    return out


@dataclass
class Scoped:
    """What a traced window shows by scope, over the chips traced."""
    forwards: int                 # XLA Modules events, summed over chips
    scope_s: dict[str, float]     # device seconds by scope, over every forward
    kernels: dict[str, str]       # layer -> the Pallas kernel it ran
    kernel_count: int             # Pallas kernels in the compiled text
    scoped_kernels: int           # of them, those under a layer's scope
    forward_s: float              # the forwards' device time
    gap_s: float                  # of it, time in which no op ran
    outside_ops: int = 0          # op events that start in no forward
    unknown_ops: int = 0          # op events absent from the compiled text

    def layer_s(self) -> dict[str, float]:
        """Device seconds by layer, its parts summed."""
        out: dict[str, float] = collections.Counter()
        for scope, s in self.scope_s.items():
            out[scope.split("/")[0]] += s
        return dict(out)

    def per_forward_ms(self, seconds: float) -> float:
        return 1e3 * seconds / self.forwards


def reduce_scopes(xspace, hlo_text: str) -> Scoped | None:
    """Device time by scope and the forwards' gaps; ``None`` when the
    compiled text names no scope."""
    ops = devtrace.parse_hlo(hlo_text)
    scope = scopes(ops)
    if not any(scope_of(op) for op in ops.values()):
        return None
    kernels = {scope[n].split("/")[0]: op.kernel for n, op in ops.items()
               if op.kernel and scope_of(op)}
    scope_s: dict[str, float] = collections.Counter()
    forwards, forward_s, gap_s, outside, unknown = 0, 0.0, 0.0, 0, 0
    for plane in xspace.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if devtrace.OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        runs = sorted((e.start_ns, e.end_ns) for e in lines[MODULES_LINE].events)
        starts = [s for s, _ in runs]
        inside = [[] for _ in runs]
        for e in lines[devtrace.OPS_LINE].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= runs[i][1]:
                outside += 1
                continue
            end = min(e.end_ns, runs[i][1])
            inside[i].append((e.start_ns, end))
            name = devtrace._NAME.match(e.name).group(1)
            if name not in scope:
                unknown += 1
            scope_s[scope.get(name, UNSCOPED)] += (end - e.start_ns) * 1e-9
        for (s, t), ivs in zip(runs, inside):
            busy = sum(b - a for a, b in devtrace._union(ivs))
            forward_s += (t - s) * 1e-9
            gap_s += (t - s - busy) * 1e-9
        forwards += len(runs)
    if not forwards:
        raise ValueError("no TPU device plane with XLA Modules and XLA Ops")
    return Scoped(forwards=forwards, scope_s=dict(scope_s), kernels=kernels,
                  kernel_count=sum(1 for op in ops.values() if op.kernel),
                  scoped_kernels=sum(1 for op in ops.values()
                                     if op.kernel and scope_of(op)),
                  forward_s=forward_s, gap_s=gap_s, outside_ops=outside,
                  unknown_ops=unknown)


# ------------------------------------------------- the numbers read from it
def im2col_ms(red: Scoped) -> float | None:
    """Device ms per forward under every ``<layer>/im2col`` scope: the
    strided convs' patches (ResNet-50's stem alone)."""
    s = sum(v for k, v in red.scope_s.items() if k.endswith("/im2col"))
    return red.per_forward_ms(s) if s > 0 else None


def forward_gap_ms(red: Scoped) -> float:
    """Device ms per forward in which no op ran, inside the forward."""
    return red.per_forward_ms(red.gap_s)


def kernel_roofline(red: Scoped, work: list[dict], peaks, kernel: str) -> float | None:
    """Percent of the roofline reached by the layers whose Pallas kernel is
    ``kernel``: their least time from ``work`` (matched by layer name) per
    forward, over their device time per forward.  ``None`` when no layer ran
    that kernel."""
    names = {n for n, k in red.kernels.items() if k == kernel}
    layers = [layer for layer in work if layer["name"] in names]
    device_s = sum(v for n, v in red.layer_s().items() if n in names)
    if not layers or device_s <= 0:
        return None
    least = sum(roofline.least_time(layer, peaks)[0] for layer in layers)
    return 100.0 * least * red.forwards / device_s
