"""From a profiler trace and the compiled program to device time per layer.

The TPU's device plane (``/device:TPU:<n>``) carries one line of events,
``XLA Ops``, whose names begin with the HLO instruction that ran
(``%_conv2d_jit.18 = f32[...] custom-call(...)``).  The compiled program's
text (``compiled.as_text()``) gives each instruction of its ENTRY computation
its ``metadata op_name``, a name stack that holds the jit of
``kernels/ops.py`` the op was traced under, and for a Mosaic kernel the
kernel's own name inside its serialized body.  :func:`classify` maps every
instruction to a category:

* ``conv1x1``: under ``jit(_conv1x1_jit)``, the 1x1 convs on either GEMM
  stationarity, with their pads and slices;
* ``conv3x3``: under ``jit(_conv2d_jit)``, the ``_conv2d_kernel`` Mosaic
  kernel and the pads and slices that feed it or read its output;
* ``stem``: under ``jit(_conv2d_jit)``, every other kernel (the strided conv's
  im2col GEMM) with its im2col pads, gathers and concatenations;
* ``glue``: every other op the model traced (max pool, mean, fc).

An op the compiler made (no ``op_name``, or the name of an argument: layout
copies, async slices, bitcasts) belongs to the category of the ops it feeds,
when they agree on one; otherwise it is glue.

The host's ``TraceAnnotation`` spans, written into the same trace on the same
clock, name what the host was doing during each gap in which no op ran.
"""
from __future__ import annotations

import base64
import bisect
import collections
import re
import statistics
from dataclasses import dataclass

CONV_JITS = {"jit(_conv1x1_jit)": "conv1x1", "jit(_conv2d_jit)": "conv2d"}
CONV2D_KERNEL = "_conv2d_kernel"
CATEGORIES = ("conv3x3", "conv1x1", "stem", "glue")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_NAME = re.compile(r"%?([\w.\-]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_KERNEL = re.compile(rb"[A-Za-z_]\w*_kernel")


@dataclass
class HloOp:
    name: str
    opcode: str
    op_name: str | None
    operands: list[str]
    kernel: str | None = None


def _skip_type(s: str, i: int) -> int:
    """Index just past the result type that starts at ``s[i]``."""
    if s[i] != "(":
        return s.index(" ", i)
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return j + 1
    raise ValueError(f"unbalanced tuple type: {s[i:i + 80]}")


def _call_args(s: str, i: int) -> str:
    """The text inside the parentheses that open at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return s[i + 1:j]
    raise ValueError(f"unbalanced operands: {s[i:i + 80]}")


def _kernel_name(line: str) -> str | None:
    m = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
    if not m:
        return None
    found = _KERNEL.search(base64.b64decode(m.group(1)))
    return found.group(0).decode() if found else None


def parse_hlo(text: str) -> dict[str, HloOp]:
    """The instructions of the ENTRY computation of ``compiled.as_text()``."""
    start = text.index("\nENTRY ")
    ops = {}
    for line in text[start:].splitlines()[1:]:
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if not m:
            continue
        i = _skip_type(line, m.end()) + 1
        paren = line.index("(", i)
        opcode = line[i:paren]
        operands = re.findall(r"%([\w.\-]+)", _call_args(line, paren))
        op_name = re.search(r'op_name="([^"]*)"', line)
        kernel = (_kernel_name(line)
                  if 'custom_call_target="tpu_custom_call"' in line else None)
        ops[m.group(1)] = HloOp(m.group(1), opcode,
                                op_name.group(1) if op_name else None,
                                operands, kernel)
    return ops


def _scope(op: HloOp) -> str | None:
    for jit, scope in CONV_JITS.items():
        if op.op_name and jit in op.op_name:
            return scope
    return None


def _walk(start: str, edges: dict[str, list[str]], stop) -> list[str]:
    """Breadth-first from ``start`` along ``edges``; the first ops for which
    ``stop`` holds on each path, not walking past them."""
    seen, found, queue = {start}, [], collections.deque(edges.get(start, ()))
    while queue:
        n = queue.popleft()
        if n in seen:
            continue
        seen.add(n)
        if stop(n):
            found.append(n)
        else:
            queue.extend(edges.get(n, ()))
    return found


def classify(ops: dict[str, HloOp]) -> dict[str, str]:
    """Category of every ENTRY instruction (see the module docstring)."""
    users = collections.defaultdict(list)
    for op in ops.values():
        for o in op.operands:
            users[o].append(op.name)
    operands = {n: [o for o in op.operands if o in ops] for n, op in ops.items()}
    cat: dict[str, str] = {}
    for n, op in ops.items():
        scope = _scope(op)
        if op.kernel and scope == "conv1x1":
            cat[n] = "conv1x1"
        elif op.kernel and scope == "conv2d":
            cat[n] = "conv3x3" if op.kernel == CONV2D_KERNEL else "stem"
    kernels = set(cat)
    for n, op in ops.items():
        if n in cat:
            continue
        scope = _scope(op)
        if scope == "conv1x1":
            cat[n] = "conv1x1"
        elif scope == "conv2d":
            near = (_walk(n, users, kernels.__contains__)
                    or _walk(n, operands, kernels.__contains__))
            cat[n] = cat[near[0]] if near else "glue"
        elif op.op_name and "jit(" in op.op_name:
            cat[n] = "glue"
    decided = set(cat)
    for n in ops:
        if n not in cat:
            fed = {cat[u] for u in _walk(n, users, decided.__contains__)}
            cat[n] = fed.pop() if len(fed) == 1 else "glue"
    return cat


@dataclass
class Reduced:
    """What a traced window shows, over the chips traced."""
    window_s: float
    busy_s: float                      # union of device op intervals, mean over chips
    category_s: dict[str, float]       # device time by category, summed over chips
    op_s: dict[str, float]             # device time by breakdown name
    gaps_s: dict[str, float]           # idle time by host annotation open in it
    modules: int                       # program executions on the device
    chips: int
    unknown_ops: int = 0               # device events absent from the program's text


def load_xspace(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _offset(host, modules, dispatch: str) -> float:
    """Nanoseconds to add to the device's times to put them on the host's
    clock.  The two clocks in a trace differ by up to a millisecond or more
    (seen by hand in a v5e trace), so each forward's start on the device is
    matched with the end of its ``dispatch`` span, after which a device that
    was waiting starts at once; the median of those differences is the shift.
    With no forward to match, the clocks are taken as they are."""
    ends = sorted(e for _, e, name in host if name == dispatch)
    starts = sorted(modules)
    if not starts or len(ends) != len(starts):
        return 0.0
    return statistics.median(e - s for e, s in zip(ends, starts))


def reduce_trace(xspace, hlo_text: str, annotations: tuple[str, ...]) -> Reduced:
    """Device time by category, busy time, and idle time by host annotation,
    over the window from the first ``annotations`` span's start to the last
    one's end.  ``annotations[0]`` is the span around each request's dispatch."""
    cats = classify(parse_hlo(hlo_text))
    host = []
    for plane in xspace.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                            if e.name in annotations)
    if not host:
        raise ValueError(f"no host span named {annotations} in the trace")
    host.sort()
    starts = [h[0] for h in host]
    t0, t1 = host[0][0], max(h[1] for h in host)
    category = dict.fromkeys(CATEGORIES, 0.0)
    op_s: dict[str, float] = collections.Counter()
    busy, modules, chips, unknown = 0.0, 0, 0, 0
    gaps: dict[str, float] = collections.Counter()
    for plane in xspace.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        chips += 1
        runs = [e.start_ns for e in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        shift = _offset(host, runs, annotations[0])
        modules += sum(1 for r in runs if t0 <= r + shift < t1)
        intervals = []
        for e in lines[OPS_LINE].events:
            s, t = max(e.start_ns + shift, t0), min(e.end_ns + shift, t1)
            if t <= s:
                continue
            name = _NAME.match(e.name).group(1)
            c = cats.get(name)
            if c is None:
                unknown += 1
                c = "glue"
            category[c] += (t - s) * 1e-9
            op_s[f"glue:{name}" if c == "glue" else c] += (t - s) * 1e-9
            intervals.append((s, t))
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_open_at(host, starts, (s + e) / 2)] += (e - s) * 1e-9
    if not chips:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    return Reduced(window_s=(t1 - t0) * 1e-9, busy_s=busy / chips,
                   category_s=category, op_s=dict(op_s),
                   gaps_s={k: v / chips for k, v in gaps.items()},
                   modules=modules, chips=chips, unknown_ops=unknown)


def _open_at(host, starts, t: float) -> str:
    """The host annotation open at time ``t``, or ``host`` when none is.
    The benchmark's annotations follow one another and never nest."""
    i = bisect.bisect_right(starts, t) - 1
    return host[i][2] if i >= 0 and t < host[i][1] else "host"


def breakdown(red: Reduced, n: int = 10) -> dict:
    """The device ops that took most time and the idle time by host span."""
    top = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(red.gaps_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
