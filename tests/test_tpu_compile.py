"""The main path's kernels compile for a TPU v5e, at ResNet-50's real widths.

No chip is needed: the TPU compiler compiles for a v5e that is described, not
attached.  Each case is one conv dispatch as ``kernels.ops`` runs it on a
TPU (``ops._on_tpu`` steered to True, so nothing is interpreted), and must
lower to exactly one Mosaic kernel.  The topology is described inside a
fixture, never at import, so only the worker that runs this file loads the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.autotune import TileConfig
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
    # the stem GEMM under a tuned entry that splits its 147 patch columns
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
