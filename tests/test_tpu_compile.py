"""The main path's kernels compile for a TPU v5e, at ResNet-50's real widths.

No chip is needed: the TPU compiler compiles for a v5e that is described, not
attached.  Each case is one conv dispatch as ``kernels.ops`` runs it on a
TPU (``ops._on_tpu`` steered to True, so nothing is interpreted), and must
lower to exactly one Mosaic kernel.  The topology is described inside a
fixture, never at import, so only the worker that runs this file loads the
TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.autotune import TileConfig
from repro.core.carla import carla_conv
from repro.core.fuse import Epilogue
from repro.kernels import ops

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
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


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
    # the stem GEMM under a tuned entry that splits its 192 patch columns
    # into two 128-lane blocks
    "stem_7x7s2_bc128": ((1, 224, 224, 3), (7, 7, 3, 64), 2, 3, False),
}
TILES = {
    "stem_7x7s2_bc128": TileConfig(bm=256, bk=64, bc=128,
                                   stationarity="activation_stationary"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    xs, ws, stride, padding, has_res = CASES[name]
    tiles = TILES.get(name)
    k = ws[-1]
    oh = (xs[1] - ws[0] + 2 * padding) // stride + 1

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if ws[0] == 1:
        def fwd(x, w, sc, bi, res):
            return ops._conv1x1_jit(x, w[0, 0], sc, bi, res, relu=True,
                                    stride=stride, impl="pallas",
                                    tiles=tiles)
    else:
        def fwd(x, w, sc, bi, res):
            return ops._conv2d_jit(x, w, sc, bi, res, relu=True,
                                   stride=stride, padding=padding,
                                   impl="pallas", tiles=tiles)

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
