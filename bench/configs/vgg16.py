"""VGG-16 for the benchmark: weights from the seed, the plain reference, the work.

The sizes come from ``vgg16.json`` beside this file.  As for ResNet-50,
nothing here imports the program under test: the weights, the reference
forward and the count of work are the benchmark's own, written from the
paper (arXiv:1409.1556, Table 1, configuration D).  The inputs and the
reference's contractions, in float32 at HIGHEST or as three bf16 passes for
the control, are ResNet-50's (``resnet50.py``).

The program's forward, ``repro.models.cnn.vgg16_apply``, takes the pytree
that :func:`build` makes: ``conv<g>_<i>`` for the 13 convs, each ``w`` an HWIO
(3, 3, C, K) filter and ``b`` a (K,) bias, then ``fc6``, ``fc7`` and ``fc8``,
each ``w`` a (C, K) matrix over the (H, W, C)-flattened map and ``b`` (K,).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.configs.resnet50 import HIGHEST, _contract, _conv, inputs, scaled

__all__ = ["build", "inputs", "program_forward", "reference", "work"]


def _convs(cfg: dict):
    """(name, group) of every conv, in order."""
    return [(f"conv{g}_{i}", g) for g, grp in enumerate(cfg["groups"], start=1)
            for i in range(1, grp["convs"] + 1)]


def _layout(cfg: dict, width: float) -> list[tuple]:
    """(path, shape, variance) of every parameter in a fixed order; a
    variance of ``None`` marks a bias (deviation 0.1)."""
    out, cin = [], cfg["in_channels"]
    fl = cfg["conv"]["kernel"]
    for name, g in _convs(cfg):
        k = scaled(cfg["groups"][g - 1]["channels"], width)
        out += [((name, "w"), (fl, fl, cin, k), 2.0 / (fl * fl * cin)),
                ((name, "b"), (k,), None)]
        cin = k
    side = cfg["image_size"] // cfg["pool"]["stride"] ** len(cfg["groups"])
    cin *= side * side
    widths = [scaled(k, width) for k in cfg["fc"]] + [cfg["num_classes"]]
    for n, k in enumerate(widths, start=6):
        gain = 2.0 if n < 6 + len(cfg["fc"]) else 1.0
        out += [((f"fc{n}", "w"), (cin, k), gain / cin), ((f"fc{n}", "b"), (k,), None)]
        cin = k
    return out


def _dense(cfg: dict, key, width: float) -> dict:
    """Every parameter cut, in the order of :func:`_layout`, from one normal
    draw: one random op keeps the program small."""
    layout = _layout(cfg, width)
    sizes = [math.prod(shape) for _, shape, _ in layout]
    normal = jax.random.normal(key, (sum(sizes),), jnp.float32)
    params: dict = {}
    i = 0
    for (path, shape, var), n in zip(layout, sizes):
        value, i = normal[i:i + n] * (0.1 if var is None else var ** 0.5), i + n
        params.setdefault(path[0], {})[path[1]] = value.reshape(shape)
    return params


def build(cfg: dict, key, width: float = 1.0) -> dict:
    """The served weights, made on the device from ``key`` in one jitted call."""
    return jax.jit(lambda k: _dense(cfg, k, width))(key)


def program_forward():
    """The entry the window drives: one jit of the program's fused forward
    through the Pallas kernels."""
    from repro.models.cnn import vgg16_apply
    return jax.jit(functools.partial(vgg16_apply, impl="pallas", fused=True))


def reference(cfg: dict, params: dict, x, precision: str = "highest"):
    """Plain float32 VGG-16 forward: (B, H, W, C) images -> logits.

    Each 3x3 conv (stride 1, pad 1) adds its bias and takes a ReLU; each group
    ends in a 2x2/2 max pool; the map is flattened (H, W, C); fc6 and fc7 add
    their biases and take a ReLU; fc8 gives the logits.
    """
    conv, pk, ps = cfg["conv"], cfg["pool"]["kernel"], cfg["pool"]["stride"]
    h = x
    for name, g in _convs(cfg):
        p = params[name]
        h = jax.nn.relu(_conv(h, p["w"], conv["stride"], conv["padding"],
                              precision) + p["b"])
        if name.endswith(f"_{cfg['groups'][g - 1]['convs']}"):
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, pk, pk, 1),
                                  (1, ps, ps, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    dot = functools.partial(jnp.dot, precision=HIGHEST)
    fcs = [f"fc{n}" for n in range(6, 7 + len(cfg["fc"]))]
    for name in fcs:
        h = _contract(dot, h, params[name]["w"], precision) + params[name]["b"]
        if name != fcs[-1]:
            h = jax.nn.relu(h)
    return h


def work(cfg: dict, params: dict, batch: int) -> list[dict]:
    """Operations and bytes of one forward at ``batch``, layer by layer, from
    the parameter shapes and the configuration's spatial sizes.  FLOPs are 2 x
    multiply-adds, taps over the zero border included.  Bytes, float32: the
    input, the weights, the bias and the output, each once.

    Kinds name what the trace files the time under, so that the benchmark's
    readers of those kinds read VGG-16 too: ``conv3x3`` for the 3x3s on the
    conv2d kernel; ``stem`` for conv1_1, whose 27 patch columns (3x3 taps of
    3 channels) fit one lane tile, so that it runs as an im2col GEMM like
    ResNet-50's stem; ``conv1x1`` for fc6-fc8, which run as 1x1 convs on a
    1x1 map, on the GEMM path."""
    def shape(a):
        return tuple(getattr(a, "shape", a))

    itemsize, size, layers = 4, cfg["image_size"], []
    for name, g in _convs(cfg):
        fh, fw, c, k = shape(params[name]["w"])
        layers.append({
            "name": name, "kind": "stem" if name == "conv1_1" else "conv3x3",
            "flops": 2 * batch * size * size * k * fh * fw * c,
            "bytes": itemsize * (batch * size * size * (c + k) + fh * fw * c * k + k)})
        if name.endswith(f"_{cfg['groups'][g - 1]['convs']}"):
            size //= cfg["pool"]["stride"]
    for n in range(6, 7 + len(cfg["fc"])):
        c, k = shape(params[f"fc{n}"]["w"])
        layers.append({"name": f"fc{n}", "kind": "conv1x1", "flops": 2 * batch * c * k,
                       "bytes": itemsize * (batch * (c + k) + c * k + k)})
    return layers
