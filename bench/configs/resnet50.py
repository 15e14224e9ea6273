"""ResNet-50 for the benchmark: weights from the seed, the plain reference, the work.

The configuration files beside this one (``resnet50_*.json``) give the sizes;
everything here reads them, so a configuration that changes only numbers needs
no code.  Nothing here imports the program under test: the weights, the
pruning, the reference forward and the count of work are the benchmark's own,
written from the papers, so the comparison that decides ``correct`` and the
roofline arithmetic cannot move with the program.

The program's forward, ``repro.models.cnn.resnet50_apply``, takes the pytree
that :func:`build` makes: ``conv1``/``bn1``, one ``<stage>_b<i>`` dict per
bottleneck (``c1``, ``c3`` and ``proj`` as (C, K) matrices, ``c2`` as a
(3, 3, C, K) HWIO filter, each followed by its folded batch norm ``bn*`` with
``scale`` and ``bias``), and ``fc`` with ``w``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


# --------------------------------------------------------------- the weights
def scaled(c: int, width: float) -> int:
    """A channel count at ``width`` times the published one (tests only)."""
    return max(4, int(c * width))


def _layout(cfg: dict, width: float) -> list[tuple]:
    """(path, shape, draw) of every parameter, in a fixed order.  ``draw`` is
    the fan-in of a conv or fc weight (normal, variance 1/fan-in), ``scale``
    or ``bias`` for a batch norm folded into its conv's epilogue: a scale
    uniform in [0.5, 1.5] and a bias normal with deviation 0.1, so that a path
    that drops either reads far off the reference."""
    w = functools.partial(scaled, width=width)
    stem = cfg["stem"]
    fl, c0 = stem["kernel"], w(stem["channels"])

    def bn(*path, k):
        return [(path + ("scale",), (k,), "scale"), (path + ("bias",), (k,), "bias")]

    out = [(("conv1",), (fl, fl, cfg["in_channels"], c0), fl * fl * cfg["in_channels"])]
    out += bn("bn1", k=c0)
    cin = c0
    for st in cfg["stages"]:
        mid, k = w(st["mid"]), w(st["out"])
        for b in range(st["blocks"]):
            name, ic = f"{st['name']}_b{b}", cin if b == 0 else k
            out += [((name, "c1"), (ic, mid), ic)] + bn(name, "bn1", k=mid)
            out += [((name, "c2"), (3, 3, mid, mid), 9 * mid)] + bn(name, "bn2", k=mid)
            out += [((name, "c3"), (mid, k), mid)] + bn(name, "bn3", k=k)
            if b == 0:
                out += [((name, "proj"), (ic, k), ic)] + bn(name, "bnp", k=k)
        cin = k
    return out + [(("fc", "w"), (cin, cfg["num_classes"]), cin)]


def _dense(cfg: dict, key, width: float) -> dict:
    """Every weight from two draws, one normal and one uniform, cut in the
    order of :func:`_layout`: one random op each keeps the program small."""
    layout = _layout(cfg, width)
    sizes = [math.prod(shape) for _, shape, _ in layout]
    kn, ku = jax.random.split(key)
    normal = jax.random.normal(kn, (sum(n for n, (_, _, d) in zip(sizes, layout)
                                        if d != "scale"),), jnp.float32)
    uniform = jax.random.uniform(ku, (sum(n for n, (_, _, d) in zip(sizes, layout)
                                          if d == "scale"),), jnp.float32, 0.5, 1.5)
    params: dict = {}
    i = j = 0
    for (path, shape, draw), n in zip(layout, sizes):
        if draw == "scale":
            value, j = uniform[j:j + n], j + n
        else:
            value, i = normal[i:i + n] * (0.1 if draw == "bias" else draw ** -0.5), i + n
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value.reshape(shape)
    return params


def _keep(w, fraction: float):
    """Indices, ascending, of the output channels with the largest L1 norms."""
    k = w.shape[-1]
    n = max(1, int(round(k * fraction)))
    norms = jnp.sum(jnp.abs(w), axis=tuple(range(w.ndim - 1)))
    return jnp.sort(lax.top_k(norms, n)[1])


def prune(cfg: dict, params: dict) -> dict:
    """Structured channel pruning of every bottleneck (CARLA, arXiv:2010.00627,
    §IV.A, Table I): the first two convs keep ``keep_fraction`` of their output
    channels by L1 norm, each kept set also selects the next conv's input
    channels (1x1a -> 3x3 -> 1x1b), and the folded batch norms follow their
    conv.  Block outputs, projections, the stem and fc stay dense."""
    fraction = cfg["keep_fraction"]
    if fraction >= 1.0:
        return params
    out = dict(params)
    for st in cfg["stages"]:
        for b in range(st["blocks"]):
            name = f"{st['name']}_b{b}"
            blk = dict(params[name])
            k1 = _keep(blk["c1"], fraction)
            k2 = _keep(blk["c2"], fraction)
            blk["c1"] = blk["c1"][:, k1]
            blk["bn1"] = {n: v[k1] for n, v in blk["bn1"].items()}
            blk["c2"] = blk["c2"][:, :, k1][..., k2]
            blk["bn2"] = {n: v[k2] for n, v in blk["bn2"].items()}
            blk["c3"] = blk["c3"][k2]
            out[name] = blk
    return out


def build(cfg: dict, key, width: float = 1.0) -> dict:
    """The served weights, made on the device from ``key`` in one jitted call."""
    return jax.jit(lambda k: prune(cfg, _dense(cfg, k, width)))(key)


def inputs(cfg: dict, key, pool: int, batch: int) -> list:
    """``pool`` distinct batches of images, each (batch, H, W, C), made on the
    device in one jitted call."""
    shape = (pool, batch, cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    return list(jax.jit(lambda k: tuple(jax.random.normal(k, shape, jnp.float32)))(key))


def program_forward():
    """The entry the window drives: one jit of the program's fused forward
    through the Pallas kernels."""
    from repro.models.cnn import resnet50_apply
    return jax.jit(functools.partial(resnet50_apply, impl="pallas", fused=True))


# ------------------------------------------------------------ the reference
def _round_bf16(a):
    """``a`` rounded to the nearest bf16 (ties to even), kept in float32.
    Done on the bits: a float32 -> bf16 -> float32 round trip may be folded
    away by a compiler that allows excess precision, as XLA's TPU backend
    does, which turns the three passes below into one."""
    bits = lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _split_bf16(a):
    hi = _round_bf16(a)
    return hi, _round_bf16(a - hi)


def _contract(op, a, b, precision: str):
    """``op(a, b)`` in float32 at HIGHEST, or as the three bf16 passes that
    XLA's ``Precision.HIGH`` makes of it (hi*hi + hi*lo + lo*hi).  The
    products of bf16 values are exact in float32, so the second is that
    precision on any backend."""
    if precision == "highest":
        return op(a, b)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _conv(x, w, stride: int, padding: int, precision: str):
    if w.ndim == 2:
        w = w[None, None]
    op = functools.partial(lax.conv_general_dilated,
                           window_strides=(stride, stride),
                           padding=[(padding, padding)] * 2,
                           dimension_numbers=("NHWC", "HWIO", "NHWC"),
                           precision=HIGHEST)
    return _contract(op, x, w, precision)


def _norm(x, bn):
    return x * bn["scale"] + bn["bias"]


def reference(cfg: dict, params: dict, x, precision: str = "highest"):
    """Plain float32 ResNet-50 v1 forward: (B, H, W, C) images -> logits.

    He et al. (arXiv:1512.03385) Table 1, as the configuration gives it: a
    7x7/2 stem with batch norm and ReLU, a 3x3/2 max pool whose windows start
    at 0, 2, ... with the right edge padded (the original Caffe model's ceil
    mode), bottlenecks 1x1 -> 3x3 -> 1x1 with the stride on the first 1x1 and
    a projection on each stage's first block, the shortcut added before the
    last ReLU, a global mean and fc.  Batch norms are folded to scale and bias.
    """
    stem = cfg["stem"]
    h = jax.nn.relu(_norm(_conv(x, params["conv1"], stem["stride"],
                                stem["kernel"] // 2, precision), params["bn1"]))
    pk, ps = stem["pool"]["kernel"], stem["pool"]["stride"]
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, pk, pk, 1),
                          (1, ps, ps, 1), "SAME")
    for st in cfg["stages"]:
        for b in range(st["blocks"]):
            blk = params[f"{st['name']}_b{b}"]
            stride = st["stride"] if b == 0 else 1
            sc = h
            if "proj" in blk:
                sc = _norm(_conv(h, blk["proj"], stride, 0, precision), blk["bnp"])
            y = jax.nn.relu(_norm(_conv(h, blk["c1"], stride, 0, precision), blk["bn1"]))
            y = jax.nn.relu(_norm(_conv(y, blk["c2"], 1, 1, precision), blk["bn2"]))
            h = jax.nn.relu(_norm(_conv(y, blk["c3"], 1, 0, precision), blk["bn3"]) + sc)
    feats = jnp.mean(h, axis=(1, 2))
    dot = functools.partial(jnp.dot, precision=HIGHEST)
    return _contract(dot, feats, params["fc"]["w"], precision)


# ------------------------------------------------------------------ the work
def work(cfg: dict, params: dict, batch: int) -> list[dict]:
    """Operations and bytes of one forward at ``batch``, layer by layer.

    Taken from the parameter shapes (so a pruned network counts its pruned
    work) and the configuration's spatial sizes, never from which kernel ran,
    its padding or its tiles.  FLOPs are 2 x multiply-adds, taps over the zero
    border included (He et al.'s count).  Bytes, float32: the input elements
    the layer reads (a strided 1x1 reads every ``stride``-th pixel) once, the
    weights once, the output written once, and the fused epilogue's operands:
    the folded scale and bias, and the shortcut where it is added.
    """
    def shape(a):
        return tuple(getattr(a, "shape", a))

    itemsize = 4
    layers = []

    def conv(name, kind, w, hin, stride, padding, residual=False):
        w = shape(w)
        fh, fw, c, k = w if len(w) == 4 else (1, 1) + w
        hout = (hin + 2 * padding - fh) // stride + 1
        read = hin * hin * c if fh > 1 else hout * hout * c
        out = hout * hout * k
        elems = batch * read + fh * fw * c * k + batch * out + 2 * k
        if residual:
            elems += batch * out
        layers.append({"name": name, "kind": kind,
                       "flops": 2 * batch * hout * hout * k * fh * fw * c,
                       "bytes": itemsize * elems})
        return hout

    stem = cfg["stem"]
    size = conv("conv1", "stem", params["conv1"], cfg["image_size"],
                stem["stride"], stem["kernel"] // 2)
    size = -(-size // stem["pool"]["stride"])  # the max pool
    for st in cfg["stages"]:
        for b in range(st["blocks"]):
            name = f"{st['name']}_b{b}"
            blk = params[name]
            stride = st["stride"] if b == 0 else 1
            if "proj" in blk:
                conv(f"{name}_proj", "conv1x1", blk["proj"], size, stride, 0)
            mid = conv(f"{name}_1x1a", "conv1x1", blk["c1"], size, stride, 0)
            conv(f"{name}_3x3", "conv3x3", blk["c2"], mid, 1, 1)
            size = conv(f"{name}_1x1b", "conv1x1", blk["c3"], mid, 1, 0,
                        residual=True)
    c, k = shape(params["fc"]["w"])
    layers.append({"name": "fc", "kind": "fc", "flops": 2 * batch * c * k,
                   "bytes": itemsize * (batch * c + c * k + batch * k)})
    return layers
