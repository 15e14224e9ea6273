"""The main path's kernels compile for a TPU v5e, at ResNet-50's and
VGG-16's real widths.

No chip is needed: the TPU compiler compiles for a v5e that is described, not
attached.  Each case is one conv dispatch as ``kernels.ops`` runs it on a
TPU (``ops._on_tpu`` steered to True, so nothing is interpreted), and must
lower to exactly one Mosaic kernel.  The topology is described inside a
fixture, never at import, so only the worker that runs this file loads the
TPU library.
"""
import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.carla import carla_conv
from repro.core.fuse import Epilogue
from repro.kernels import matmul_act_stationary, ops

KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        mp.setattr(ops, "_on_tpu", lambda: True)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe a v5e with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        # a jit traced earlier in this process on the CPU's shapes cached a
        # jaxpr with its kernels interpreted, which this file's jits of the
        # same shapes would reuse; drop it, and what this file traces
        jax.clear_caches()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
            jax.clear_caches()


# (x shape, w shape, stride, padding, residual): one dispatch each
CASES = {
    # the 7x7/2 stem, routed by ops through the im2col GEMM
    "stem_7x7s2": ((1, 224, 224, 3), (7, 7, 3, 64), 2, 3, False),
    # 1x1 with 64 input channels: conv2_b0_1x1a, 3136x64 -> 64
    "1x1_c64": ((1, 56, 56, 64), (1, 1, 64, 64), 1, 0, False),
    # pruned conv2 block-closing 1x1: 32 input channels, residual fused
    "1x1_c32_pruned": ((1, 56, 56, 32), (1, 1, 32, 256), 1, 0, True),
    "3x3_56x56x64": ((1, 56, 56, 64), (3, 3, 64, 64), 1, 1, False),
    # conv5 1x1 at M=49 rows: weight-stationary
    "1x1_conv5_ws": ((1, 7, 7, 2048), (1, 1, 2048, 512), 1, 0, False),
    "1x1_c256_b8": ((8, 56, 56, 256), (1, 1, 256, 64), 1, 0, False),
    # the stem GEMM with its 192 patch columns in two 128-lane blocks
    "stem_7x7s2_bc128": ((1, 224, 224, 3), (7, 7, 3, 64), 2, 3, False),
    # VGG-16's conv1_2 and conv2_1: the conv2d kernel in row blocks
    "vgg_conv1_2": ((1, 224, 224, 64), (3, 3, 64, 64), 1, 1, False),
    "vgg_conv2_1": ((1, 112, 112, 64), (3, 3, 64, 128), 1, 1, False),
    # VGG-16's conv1_1: 27 patch columns, the unit-stride im2col GEMM
    "vgg_conv1_1": ((1, 224, 224, 3), (3, 3, 3, 64), 1, 1, False),
    # VGG-16's fc6 at batch 1: the weight-stationary GEMM in C blocks
    "vgg_fc6": ((1, 1, 1, 25088), (1, 1, 25088, 4096), 1, 0, False),
    # a 224x224 plane of 256 channels: row blocks of the whole C, above the
    # 128 lanes of a default channel block
    "3x3_224x224x256_rows": ((1, 224, 224, 256), (3, 3, 256, 256), 1, 1, False),
    # the folded 3x3s of the offline cells (2 or 4 columns in 128 lanes)
    "3x3_56x56x64_b32": ((32, 56, 56, 64), (3, 3, 64, 64), 1, 1, False),
    "3x3_56x56x32_b32_pruned": ((32, 56, 56, 32), (3, 3, 32, 32), 1, 1, False),
    "3x3_28x28x64_b32_pruned": ((32, 28, 28, 64), (3, 3, 64, 64), 1, 1, False),
    # the 14x14 and 7x7 3x3s of the offline cells: several images a step
    "3x3_14x14x256_b32": ((32, 14, 14, 256), (3, 3, 256, 256), 1, 1, False),
    "3x3_7x7x512_b32": ((32, 7, 7, 512), (3, 3, 512, 512), 1, 1, False),
    "3x3_14x14x128_b32_pruned": ((32, 14, 14, 128), (3, 3, 128, 128), 1, 1,
                                 False),
    "3x3_7x7x256_b32_pruned": ((32, 7, 7, 256), (3, 3, 256, 256), 1, 1, False),
}
# cases that call a kernel with blocks of their own, not the route's
BLOCKS = {"stem_7x7s2_bc128": dict(bm=256, bk=64, bc=128)}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    xs, ws, stride, padding, has_res = CASES[name]
    k = ws[-1]
    oh = (xs[1] - ws[0] + 2 * padding) // stride + 1

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if name in BLOCKS:
        def fwd(x, w, sc, bi, res):
            p, wf = ops._im2col(x, w, stride, padding)
            b, oh, ow, kk = p.shape
            out = matmul_act_stationary(p.reshape(b * oh * ow, kk), wf,
                                        scale=sc, bias=bi, relu=True,
                                        interpret=False, **BLOCKS[name])
            return out.reshape(b, oh, ow, k)
    elif ws[0] == 1:
        def fwd(x, w, sc, bi, res):
            return ops._conv1x1_jit(x, w[0, 0], sc, bi, res, relu=True,
                                    stride=stride, impl="pallas")
    else:
        def fwd(x, w, sc, bi, res):
            return ops._conv2d_jit(x, w, sc, bi, res, relu=True,
                                   stride=stride, padding=padding,
                                   impl="pallas")

    res = spec(xs[0], oh, oh, k) if has_res else None
    compiled = jax.jit(fwd).lower(spec(*xs), spec(*ws), spec(k), spec(k),
                                  res).compile()
    assert compiled.as_text().count(KERNEL_CALL) == 1


def _named_custom_calls(text: str) -> list[str]:
    """The ``op_name`` of every Mosaic kernel in a compiled program's text."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines() if KERNEL_CALL in line]


def _stem_and_bottleneck(spec):
    """ResNet-50's stem and its first bottleneck, each conv named as
    ``resnet50_apply`` names it."""
    def fwd(x, w1, proj, c1, c2, c3):
        ep = Epilogue(relu=True)
        h = carla_conv(x, w1, stride=2, padding=3, impl="pallas", epilogue=ep,
                       name="conv1")[:, ::2, ::2]
        sc = carla_conv(h, proj, impl="pallas", name="conv2_b0_proj")
        y = carla_conv(h, c1, impl="pallas", epilogue=ep, name="conv2_b0_1x1a")
        y = carla_conv(y, c2, padding=1, impl="pallas", epilogue=ep,
                       name="conv2_b0_3x3")
        return carla_conv(y, c3, impl="pallas", name="conv2_b0_1x1b",
                          epilogue=Epilogue(relu=True, residual=sc))
    args = (spec(1, 224, 224, 3), spec(7, 7, 3, 64), spec(64, 256),
            spec(64, 64), spec(3, 3, 64, 64), spec(64, 256))
    kernels = {"conv1": "_mm_act_stationary_kernel",
               "conv2_b0_proj": "_mm_act_stationary_kernel",
               "conv2_b0_1x1a": "_mm_act_stationary_kernel",
               "conv2_b0_3x3": "_conv2d_kernel",
               "conv2_b0_1x1b": "_mm_act_stationary_kernel"}
    return fwd, args, kernels


def _conv5_1x1(spec):
    """A conv5 1x1 at batch 1 (49 rows): the weight-stationary GEMM."""
    def fwd(x, w):
        return carla_conv(x, w, impl="pallas", name="conv5_b1_1x1a")
    return fwd, (spec(1, 7, 7, 2048), spec(2048, 512)), \
        {"conv5_b1_1x1a": "_mm_weight_stationary_kernel"}


@pytest.mark.parametrize("program", [_stem_and_bottleneck, _conv5_1x1])
def test_layer_and_kernel_names_reach_the_compiled_program(program, one_chip):
    """Under an outer jit, as users run the forward, every kernel carries its
    layer's scope and its own ``pallas_call`` name in ``op_name``, and the
    stem's patches carry ``im2col``: the names a profiler trace shows."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fwd, args, kernels = program(spec)
    text = jax.jit(fwd).lower(*args).compile().as_text()
    calls = _named_custom_calls(text)
    assert len(calls) == len(kernels)
    for layer, kernel in kernels.items():
        assert sum(f"/{layer}/" in c and f"/{kernel}/" in c for c in calls) == 1
    if "conv1" in kernels:
        assert re.search(r'op_name="[^"]*/conv1/jit\(_conv2d_jit\)/im2col/', text)
        assert sum("/conv1/" in c and "/gemm/" in c for c in calls) == 1


def test_vgg16_names_reach_the_compiled_forward(one_chip):
    """VGG-16's forward, at a quarter of its widths on 32x32 images, names
    its convs, pools and fcs as the paper does, conv1_1's patches and GEMM,
    and runs the other 3x3s on ``_conv2d_kernel`` and the fcs on the
    weight-stationary GEMM."""
    from repro.models.cnn import vgg16_apply, vgg16_init
    params = jax.eval_shape(lambda k: vgg16_init(k, width=0.25, image_size=32),
                            jax.random.PRNGKey(0))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)))
    text = jax.jit(lambda p, x: vgg16_apply(p, x, impl="pallas")).lower(
        *args).compile().as_text()
    calls = _named_custom_calls(text)
    assert len(calls) == 16
    assert sum("/conv1_1/" in c and "/gemm/" in c for c in calls) == 1
    assert re.search(r'op_name="[^"]*/conv1_1/jit\(_conv2d_jit\)/im2col/', text)
    for layer in ("conv1_2", "conv3_3", "conv5_3"):
        assert sum(f"/{layer}/" in c and "/_conv2d_kernel/" in c
                   for c in calls) == 1
    for layer in ("fc6", "fc7", "fc8"):
        assert sum(f"/{layer}/" in c and "/_mm_weight_stationary_kernel/" in c
                   for c in calls) == 1
    assert re.search(r'op_name="[^"]*/pool5/', text)


def test_resnet50_keeps_one_row_block_and_one_c_block():
    """Every ResNet-50 3x3, dense and pruned, at batch 1 and 32, runs its
    whole plane in one grid step, folded (C = 64: conv2 dense, conv3 pruned;
    C = 32: conv2 pruned) or not, and every weight-stationary 1x1 (conv5's,
    49 rows at batch 1) one C block: the program the ResNet-50 cells ran
    before row and C blocks existed."""
    from repro.core import route
    from repro.core.modes import Stationarity, select_stationarity
    from repro.core.networks import (
        resnet50_conv_layers,
        resnet50_projection_shortcuts,
    )
    from repro.kernels.conv2d import row_block
    from repro.kernels.matmul import ws_blocks
    ws, folded = 0, collections.Counter()
    for sparse in (False, True):
        layers = (resnet50_conv_layers(sparse)
                  + resnet50_projection_shortcuts(sparse))
        for l in layers:
            for batch in (1, 32):
                if l.FL == 3:
                    xs, w_s = (batch, l.IL, l.IL, l.IC), (3, 3, l.IC, l.K)
                    xk, wk, pk = route.conv2d_kernel_shapes(xs, w_s, 1, 1)
                    assert row_block(xk, wk, padding=pk) == l.IL, l
                    folded[sparse, l.IC] += xk != xs
                rows = batch * (l.IL // l.S) ** 2
                if l.FL == 1 and select_stationarity(rows) == \
                        Stationarity.WEIGHT_STATIONARY:
                    assert ws_blocks(l.IC, l.K) == (128, l.IC), l
                    ws += 1
    assert ws == 2 * 7   # conv5's six 1x1s and its projection at b1, twice
    # 3 dense conv2 3x3s (64), 3 pruned conv2 (32) and 4 pruned conv3 (64)
    assert {k: v // 2 for k, v in folded.items() if v} == \
        {(False, 64): 3, (True, 32): 3, (True, 64): 4}


# layer scope: (x shape, K) of the folded 3x3s the cells run
FOLDED = {
    "vgg16_conv1_2": ((1, 224, 224, 64), 64),
    "vgg16_conv2_1": ((1, 112, 112, 64), 128),
    "resnet50_conv2_3x3_b1": ((1, 56, 56, 64), 64),
    "resnet50_conv2_3x3_b32": ((32, 56, 56, 64), 64),
    "pruned_conv2_3x3_b32": ((32, 56, 56, 32), 32),
    "pruned_conv3_3x3_b32": ((32, 28, 28, 64), 64),
}


@pytest.mark.parametrize("layer", list(FOLDED))
def test_folded_3x3_compiles_as_one_conv2d_kernel_in_its_scope(layer, one_chip):
    """A folded 3x3, inside its layer's scope as ``carla_conv`` runs it, is
    one ``_conv2d_kernel`` within VMEM, its fold runs under
    ``<layer>/.../col_fold`` (the scope ``bench/layers.py`` shows), and
    nothing in it is a gather."""
    xs, k = FOLDED[layer]

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fwd(x, w, b):
        return carla_conv(x, w, padding=1, impl="pallas", name=layer,
                          epilogue=Epilogue(bias=b, relu=True))

    text = jax.jit(fwd).lower(spec(*xs), spec(3, 3, xs[3], k),
                              spec(k)).compile().as_text()
    calls = _named_custom_calls(text)
    assert len(calls) == 1
    assert f"/{layer}/" in calls[0] and "/_conv2d_kernel/" in calls[0]
    assert re.search(rf'op_name="[^"]*/{layer}/jit\(_conv2d_jit\)/col_fold/',
                     text)
    assert not re.search(r"\bgather\(", text)


def test_stem_compiles_without_a_gather(one_chip):
    """The stem's patches come from a space-to-depth of its input, so the
    compiled forward holds no gather under ``conv1`` (a strided index there
    lowers to one gather per tap, which took most of the stem's time on a
    v5e), and the stem is one Mosaic kernel, its GEMM."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fwd, args, _ = _stem_and_bottleneck(spec)
    text = jax.jit(fwd).lower(*args).compile().as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"\bgather\(", line) and "/conv1/" in line]
    assert gathers == []
    stem = [c for c in _named_custom_calls(text) if "/conv1/" in c]
    assert len(stem) == 1 and "/gemm/" in stem[0]
