"""ResNet-50 and VGG-16 built on ``carla_conv`` — the paper's benchmark CNNs.

Every convolution goes through the CARLA mode dispatcher, so running these
models exercises all four dataflows (7x7 decomposed, 3x3 serial accumulation,
1x1 feature-stationary, 1x1 weight-stationary).  ``network_plan`` returns the
per-layer mode + analytic cost — the exact tables behind the paper's Figs 8-10.

The forwards run **fused by default**: inference-folded BN (scale/bias), a
conv's bias, ReLU, and the bottleneck residual add ride the kernels' flush
epilogue (``core.fuse.Epilogue``), so each conv output crosses HBM exactly
once — in particular the shortcut add is fused into the block's last 1x1 conv.
``fused=False`` runs the same math as separate element-wise ops (the parity
oracle, and the unfused baseline for the bytes-saved benchmarks).

VGG-16 is configuration D of Simonyan & Zisserman (arXiv:1409.1556, Table 1)
as published: 13 3x3 convs with biases, five 2x2/2 max pools, and fc6-fc8
with biases, run through ``carla_conv`` as 1x1 convs on a (B, 1, 1, C) map,
so at a small batch the classifier streams its weights through the
weight-stationary GEMM.  Its 224x224 and 112x112 3x3s run the conv2d kernel
in row blocks, and conv1_1 (3 channels) runs as an im2col GEMM.

Supports a ``width`` scale factor so smoke tests can instantiate the same
topology at reduced width, and the structured-sparse variant (§IV.A):
``resnet50_prune`` walks a dense pytree and prunes channels by L1 importance
— residual-aware (masks propagate 1x1a -> 3x3 -> 1x1b through each
bottleneck; the shortcut trunk stays dense per Table I) — and
``resnet50_apply(..., sparse=True | keep_fractions=...)`` runs the pruned
network through the same fused dispatch path, tagging every pruned dispatch
with its dense twin so telemetry reports keep-fraction and pruned-vs-dense
MACs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.carla import carla_conv, plan_conv
from repro.core.fuse import Epilogue
from repro.core.sparsity import (
    SparsityTag,
    prune_bn,
    prune_conv_weights,
    topk_channel_mask,
)


def _conv_init(key, fl: int, cin: int, k: int):
    fan_in = fl * fl * cin
    return jax.random.normal(key, (fl, fl, cin, k), jnp.float32) * fan_in ** -0.5


def _bn_init(k: int):
    return {"scale": jnp.ones((k,), jnp.float32),
            "bias": jnp.zeros((k,), jnp.float32)}


def _bn(params, x):
    """Inference-folded batch norm (scale+shift; stats folded into weights),
    or a conv's bias alone when ``params`` has no ``scale``."""
    if "scale" in params:
        x = x * params["scale"]
    return x + params["bias"]


def _conv_bn(x, w, bn, *, fused: bool, relu: bool = False,
             residual=None, stride: int = 1, padding: int = 0,
             impl: str = "auto", name: str = "conv", sparsity=None):
    """conv + folded-BN (+residual) (+ReLU), fused into the kernel flush or
    as the unfused op-by-op sequence (the parity/bytes baseline)."""
    if fused:
        ep = Epilogue(scale=None if bn is None else bn.get("scale"),
                      bias=None if bn is None else bn["bias"],
                      relu=relu, residual=residual)
        return carla_conv(x, w, stride=stride, padding=padding, impl=impl,
                          epilogue=ep, name=name, sparsity=sparsity)
    y = carla_conv(x, w, stride=stride, padding=padding, impl=impl,
                   name=name, sparsity=sparsity)
    if bn is not None:
        y = _bn(bn, y)
    if residual is not None:
        y = y + residual
    return jax.nn.relu(y) if relu else y


def _classifier(params, x):
    """Global average pool + fc.  The fc runs at full fp32 precision: on a
    TPU the default would round its operands to bf16, and the model is fp32."""
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["fc"]["w"].astype(x.dtype),
                   precision=jax.lax.Precision.HIGHEST)


# ------------------------------- ResNet-50 -----------------------------------
RESNET50_BLOCKS = {"conv2": 3, "conv3": 4, "conv4": 6, "conv5": 3}


def resnet50_init(key, *, width: float = 1.0, num_classes: int = 1000,
                  sparse: bool = False):
    """Bottleneck ResNet-50; `width` scales all channel counts (smoke tests)."""
    w = lambda c: max(4, int(c * width))
    h = 0.5 if sparse else 1.0
    keys = iter(jax.random.split(key, 256))
    params = {"conv1": _conv_init(next(keys), 7, 3, w(64)),
              "bn1": _bn_init(w(64))}
    groups = [("conv2", 3, w(64), w(64), w(256)),
              ("conv3", 4, w(256), w(128), w(512)),
              ("conv4", 6, w(512), w(256), w(1024)),
              ("conv5", 3, w(1024), w(512), w(2048))]
    for gname, n_blocks, cin, mid, cout in groups:
        midp = max(2, int(mid * h))
        for b in range(n_blocks):
            ic = cin if b == 0 else cout
            blk = {
                "c1": _conv_init(next(keys), 1, ic, midp)[0, 0],
                "bn1": _bn_init(midp),
                "c2": _conv_init(next(keys), 3, midp, midp),
                "bn2": _bn_init(midp),
                "c3": _conv_init(next(keys), 1, midp, cout)[0, 0],
                "bn3": _bn_init(cout),
            }
            if b == 0:
                blk["proj"] = _conv_init(next(keys), 1, ic, cout)[0, 0]
                blk["bnp"] = _bn_init(cout)
            params[f"{gname}_b{b}"] = blk
    params["fc"] = {"w": jax.random.normal(next(keys),
                                           (w(2048), num_classes),
                                           jnp.float32) * w(2048) ** -0.5}
    return params


def _group_keep_fraction(keep_fractions, gname: str) -> float:
    """Resolve a scalar or per-group-dict keep_fractions for one group."""
    if isinstance(keep_fractions, dict):
        return float(keep_fractions.get(gname, 1.0))
    return float(keep_fractions)


def resnet50_prune(params, keep_fractions=0.5):
    """Residual-aware structured pruning of a dense ``resnet50_init`` pytree.

    Per bottleneck block (paper Table I): the first two convs' output
    channels are pruned by L1 importance, each kept-channel mask propagates
    to the next conv's *input* channels (1x1a -> 3x3 -> 1x1b), and the
    folded-BN scale/bias vectors are pruned alongside their conv so the
    fused epilogue operands stay consistent.  The block-closing 1x1 keeps
    its output channels and the shortcut trunk (conv1, projections, block
    outputs, fc) stays dense, so every residual add still lines up.

    keep_fractions: a scalar applied to every group, or a dict keyed by
    group name (``"conv2"``..``"conv5"``; missing groups stay dense).
    Returns ``(pruned_params, masks)`` with ``masks[f"{g}_b{b}"] = (m1, m2)``
    — the kept-channel masks of the block's first and second conv.
    """
    pruned = dict(params)
    masks: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    for gname, nb in RESNET50_BLOCKS.items():
        kf = _group_keep_fraction(keep_fractions, gname)
        for b in range(nb):
            bname = f"{gname}_b{b}"
            blk = params[bname]
            if kf >= 1.0:
                masks[bname] = (np.ones(blk["c1"].shape[-1], bool),
                                np.ones(blk["c2"].shape[-1], bool))
                continue
            m1 = topk_channel_mask(blk["c1"], kf)
            m2 = topk_channel_mask(blk["c2"], kf)
            nblk = dict(blk)
            nblk["c1"] = prune_conv_weights(blk["c1"], m1)
            nblk["bn1"] = prune_bn(blk["bn1"], m1)
            nblk["c2"] = prune_conv_weights(blk["c2"], m2, keep_in=m1)
            nblk["bn2"] = prune_bn(blk["bn2"], m2)
            # block-closing 1x1: input channels follow m2, outputs stay dense
            nblk["c3"] = prune_conv_weights(blk["c3"], keep_in=m2)
            pruned[bname] = nblk
            masks[bname] = (m1, m2)
    return pruned, masks


def resnet50_apply(params, x, *, impl: str = "auto", fused: bool = True,
                   sparse: bool = False, keep_fractions=None):
    """x: (B, H, W, 3) -> (B, num_classes).  All convs via carla_conv.

    fused=True (default): BN + ReLU (+ the bottleneck residual add, fused
    into the last 1x1 conv of each block) ride the kernel flush epilogue.

    sparse=True (or an explicit ``keep_fractions``, scalar or per-group
    dict) runs the structured-sparse variant: ``params`` is pruned via
    ``resnet50_prune`` and the pruned network runs through the same fused
    dispatch path, with every pruned dispatch tagged by its dense twin
    (``SparsityTag``) so traced spans carry keep-fraction / dense-twin MACs.
    A pytree that is *already* pruned runs as-is with ``sparse=False`` —
    the forward is shape-polymorphic; the flags exist to prune and to tag.

    The work runs under named scopes, which a profiler trace and the
    compiled program show: each conv (with its fused epilogue) under its
    layer's name (``conv1``, ``conv3_b0_proj``, ``conv3_b0_1x1a``,
    ``conv3_b0_3x3``, ``conv3_b0_1x1b``, ...), the max pool under
    ``maxpool``, and the mean and fc under ``head``.
    """
    if sparse and keep_fractions is None:
        keep_fractions = 0.5
    dense_dims = None
    if keep_fractions is not None:
        dense_dims = {f"{g}_b{b}": {c: params[f"{g}_b{b}"][c].shape
                                    for c in ("c1", "c2", "c3")}
                      for g, nb in RESNET50_BLOCKS.items() for b in range(nb)}
        params, _ = resnet50_prune(params, keep_fractions)

    def tag(bname, cname, w):
        if dense_dims is None:
            return None
        ds = dense_dims[bname][cname]
        if tuple(ds) == tuple(w.shape):
            return None
        return SparsityTag(dense_ic=ds[-2], dense_k=ds[-1])

    x = _conv_bn(x, params["conv1"], params["bn1"], fused=fused, relu=True,
                 stride=2, padding=3, impl=impl, name="conv1")
    # 3x3/2 maxpool
    with jax.named_scope("maxpool"):
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    for gname, nb in RESNET50_BLOCKS.items():
        for b in range(nb):
            bname = f"{gname}_b{b}"
            blk = params[bname]
            stride = 2 if (b == 0 and gname != "conv2") else 1
            sc = x
            if "proj" in blk:
                sc = _conv_bn(x, blk["proj"], blk["bnp"], fused=fused,
                              stride=stride, impl=impl, name=f"{bname}_proj")
            h = _conv_bn(x, blk["c1"], blk["bn1"], fused=fused, relu=True,
                         stride=stride, impl=impl, name=f"{bname}_1x1a",
                         sparsity=tag(bname, "c1", blk["c1"]))
            h = _conv_bn(h, blk["c2"], blk["bn2"], fused=fused, relu=True,
                         padding=1, impl=impl, name=f"{bname}_3x3",
                         sparsity=tag(bname, "c2", blk["c2"]))
            # residual add fused into the block's last 1x1 conv
            x = _conv_bn(h, blk["c3"], blk["bn3"], fused=fused, relu=True,
                         residual=sc, impl=impl, name=f"{bname}_1x1b",
                         sparsity=tag(bname, "c3", blk["c3"]))
    with jax.named_scope("head"):
        return _classifier(params, x)


# -------------------------------- VGG-16 -------------------------------------
# (convs, channels) of each group of configuration D, arXiv:1409.1556 Table 1
VGG_SPEC = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]
VGG_FC = (4096, 4096)


def vgg16_init(key, *, width: float = 1.0, num_classes: int = 1000,
               image_size: int = 224):
    """VGG-16 (D): ``conv<g>_<i>`` and ``fc6``-``fc8``, each ``{"w", "b"}``
    with ``w`` HWIO for a conv and (C, K) for an fc.  Weights normal with
    variance 2/fan-in (He et al.), which keeps the activations' scale through
    the 15 ReLU layers, fc8's 1/fan-in; biases normal with deviation 0.1.
    ``width`` scales every width (smoke tests); fc6's fan-in follows the
    map that ``image_size`` leaves after five pools, flattened (H, W, C)."""
    w = lambda c: max(4, int(c * width))
    keys = iter(jax.random.split(key, 32))

    def layer(shape, gain):
        fan_in = int(np.prod(shape[:-1]))
        return {"w": jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / fan_in) ** 0.5,
                "b": 0.1 * jax.random.normal(next(keys), shape[-1:],
                                             jnp.float32)}

    params = {}
    cin = 3
    for g, (n, c) in enumerate(VGG_SPEC, start=1):
        for i in range(1, n + 1):
            params[f"conv{g}_{i}"] = layer((3, 3, cin, w(c)), 2.0)
            cin = w(c)
    side = image_size // 2 ** len(VGG_SPEC)
    cin = side * side * cin
    for name, k in zip(("fc6", "fc7"), VGG_FC):
        params[name] = layer((cin, w(k)), 2.0)
        cin = w(k)
    params["fc8"] = layer((cin, num_classes), 1.0)
    return params


def vgg16_apply(params, x, *, impl: str = "auto", fused: bool = True):
    """x: (B, H, W, 3) -> (B, num_classes) logits.  All convs and the three
    fcs via ``carla_conv``, each under its layer's scope (``conv1_1`` ...
    ``conv5_3``, ``fc6``, ``fc7``, ``fc8``; conv1_1's patches and GEMM under
    ``conv1_1/im2col`` and ``conv1_1/gemm``), the pools under ``pool1`` ...
    ``pool5`` (``pool5`` also flattens).  No dropout: inference."""
    for g, (n, _) in enumerate(VGG_SPEC, start=1):
        for i in range(1, n + 1):
            name = f"conv{g}_{i}"
            x = _conv_bn(x, params[name]["w"], {"bias": params[name]["b"]},
                         fused=fused, relu=True, padding=1, impl=impl,
                         name=name)
        with jax.named_scope(f"pool{g}"):
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
            if g == len(VGG_SPEC):
                x = x.reshape(x.shape[0], 1, 1, -1)
    for name in ("fc6", "fc7", "fc8"):
        x = _conv_bn(x, params[name]["w"], {"bias": params[name]["b"]},
                     fused=fused, relu=name != "fc8", impl=impl, name=name)
    return x.reshape(x.shape[0], -1)


def network_plan(layers) -> list:
    """Per-layer CARLA plan table (mode + cycles + DRAM + PUF)."""
    out = []
    for l in layers:
        p = plan_conv((1, l.IL, l.IL, l.IC), (l.FL, l.FL, l.IC, l.K),
                      stride=l.S, padding=l.Z, name=l.name)
        out.append(p)
    return out
