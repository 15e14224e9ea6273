"""The column fold of ``kernels.ops``: a unit-stride conv whose C is below 128
and divides it runs on the conv2d kernel with ``f = 128 // C`` adjacent
output columns folded into the lanes (``core.route.col_fold``, which
``core.route.route`` calls).

The Pallas route runs in interpret mode and is compared with ``lax.conv``
at HIGHEST.  Tolerance: 1e-4 absolute on outputs of order 1-10, as in
``tests/test_vgg16_paths.py`` (the kernel accumulates fp32 in another order
than XLA's convolution); the fold itself only moves data, so it adds no
error of its own (the weight fold is checked exact below).
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import route
from repro.core.fuse import Epilogue
from repro.kernels import ops
from repro.observability import trace


def _lax_conv(x, w, padding, scale=None, bias=None, residual=None,
              relu=False):
    y = lax.conv_general_dilated(
        x, w, (1, 1), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    if scale is not None:
        y = y * scale
    if bias is not None:
        y = y + bias
    if residual is not None:
        y = y + residual
    return jnp.maximum(y, 0.0) if relu else y


@pytest.mark.parametrize("epilogue", ["none", "scale+bias+residual+relu"])
@pytest.mark.parametrize("width", [14, 7, 16])
@pytest.mark.parametrize("c,k", [(64, 64), (32, 32), (64, 128)])
def test_folded_conv_matches_lax_conv(c, k, width, epilogue):
    """14 and 7 columns need their column groups rounded up to 8 (and 7 an
    odd last group); 16 at f = 2 gives 8 groups exactly."""
    key = jax.random.PRNGKey(c + k + width)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (2, 5, width, c))
    w = jax.random.normal(ks[1], (3, 3, c, k)) / (3 * c ** 0.5)
    f = route.col_fold(x.shape, w.shape, 1)
    assert f == 128 // c
    ep = {}
    if epilogue != "none":
        ep = dict(scale=jax.random.uniform(ks[2], (k,), minval=0.5, maxval=1.5),
                  bias=jax.random.normal(ks[3], (k,)),
                  residual=jax.random.normal(ks[4], (2, 5, width, k)),
                  relu=True)
    got = ops._conv2d_jit(x, w, ep.get("scale"), ep.get("bias"),
                          ep.get("residual"), relu=ep.get("relu", False),
                          padding=1, impl="pallas")
    want = _lax_conv(x, w, 1, **ep)
    assert got.shape == want.shape == (2, 5, width, k)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("x_shape,w_shape,stride,f", [
    ((1, 224, 224, 64), (3, 3, 64, 64), 1, 2),     # VGG-16 conv1_2
    ((1, 112, 112, 64), (3, 3, 64, 128), 1, 2),    # VGG-16 conv2_1
    ((32, 56, 56, 64), (3, 3, 64, 64), 1, 2),      # ResNet-50 conv2 3x3
    ((32, 56, 56, 32), (3, 3, 32, 32), 1, 4),      # pruned conv2 3x3
    ((32, 28, 28, 64), (3, 3, 64, 64), 1, 2),      # pruned conv3 3x3
    ((1, 224, 224, 3), (3, 3, 3, 64), 1, 1),       # conv1_1: the im2col GEMM
    ((1, 56, 56, 128), (3, 3, 128, 128), 1, 1),    # full lanes already
    ((1, 28, 28, 256), (3, 3, 256, 256), 1, 1),
    ((1, 56, 56, 64), (3, 3, 64, 64), 2, 1),       # strided: the im2col GEMM
    ((1, 56, 56, 64), (1, 1, 64, 256), 1, 1),      # a 1x1
    ((1, 14, 14, 24), (3, 3, 24, 16), 1, 1),       # 24 does not divide 128
])
def test_col_fold_decides_from_shapes(x_shape, w_shape, stride, f):
    assert route.col_fold(x_shape, w_shape, stride) == f


@pytest.mark.parametrize("c,k,fw", [(64, 64, 3), (32, 32, 3), (64, 128, 3),
                                    (32, 8, 5)])
def test_weight_fold_is_exact(c, k, fw):
    """Every weight appears once per output phase q, at the tap and input
    phase its column falls on, and every other entry is exactly 0."""
    f = 128 // c
    w = (jnp.arange(3 * fw * c * k, dtype=jnp.float32) + 1).reshape(3, fw, c, k)
    x = jnp.zeros((1, 3, 9, c))
    _, wf, *_ = ops._col_fold(x, w, None, None, None, 1, 0)
    twf = -(-(f + fw - 1) // f)
    assert wf.shape == (3, twf, f * c, f * k)
    wf = np.asarray(wf).reshape(3, twf, f, c, f, k)
    w = np.asarray(w)
    for q in range(f):
        phase = wf[:, :, :, :, q, :]
        assert np.count_nonzero(phase) == w.size
        assert sorted(phase[phase != 0]) == sorted(w.ravel())
        for t in range(twf):
            for p in range(f):
                s = f * t + p - q
                want = w[:, s] if 0 <= s < fw else np.zeros_like(w[:, 0])
                assert np.array_equal(phase[:, t, p], want)


def test_folded_rows_and_ragged_last_block(monkeypatch):
    """A folded plane in row blocks with a ragged last one: the fold pads
    the block's rows itself, the output is cut back to the plane."""
    monkeypatch.setattr(sys.modules["repro.kernels.conv2d"], "VMEM_BUDGET",
                        2**20)
    key = jax.random.PRNGKey(21)
    x = jax.random.normal(key, (1, 23, 20, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 64, 64)) / 24
    res = jax.random.normal(jax.random.fold_in(key, 2), (1, 23, 20, 64))
    xk, wk, pk = route.conv2d_kernel_shapes(x.shape, w.shape, 1, 1)
    th = ops.row_block(xk, wk, padding=pk, has_res=True)
    assert 23 % th and th < 23
    ep = Epilogue(bias=jnp.ones((64,)), residual=res, relu=True)
    with trace.capture() as tr:
        got = ops.conv2d(x, w, padding=1, impl="pallas", epilogue=ep)
    (sp,) = tr.spans
    assert sp.attrs["col_fold"] == 2 and sp.attrs["row_blocks"] == -(-23 // th)
    want = _lax_conv(x, w, 1, bias=ep.bias, residual=res, relu=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_tiles_tuned_unfolded_never_reach_the_folded_call():
    """A folded conv runs the conv2d kernel's tiles on the folded conv, and
    its span says so: ``col_fold`` 2, and a ``tile_util`` of the folded
    conv's 128-lane blocks (3x2 column taps for 9 taps of 64 channels:
    0.375), not the unfolded conv's quarter-filled ones (0.25)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 8, 64))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 64, 64)) / 24
    xk, wk, pk = route.conv2d_kernel_shapes(x.shape, w.shape, 1, 1)
    assert (xk, wk, pk) == ((1, 8, 9, 128), (3, 2, 128, 128), 0)
    with trace.capture() as tr:
        got = ops.conv2d(x, w, padding=1, impl="pallas")
    (sp,) = tr.spans
    assert sp.attrs["kernel"] == "conv2d" and sp.attrs["col_fold"] == 2
    assert sp.attrs["tile_util"] == pytest.approx(
        (9 * 64 * 64 * 6 * 8) / (3 * 2 * 128 * 128 * 6 * 8))
    assert float(jnp.max(jnp.abs(got - _lax_conv(x, w, 1)))) < 1e-4


def test_tile_util_counts_lanes_and_the_folds_zero_taps():
    """VGG-16's conv1_2 filled a quarter of the MXU unfolded; folded, a
    quarter of its weights are zero taps and the last row block is one row
    short: about 0.75."""
    got = route.tile_util_conv2d((1, 224, 224, 64), (3, 3, 64, 64),
                                    padding=1)
    xk, wk, _ = route.conv2d_kernel_shapes((1, 224, 224, 64),
                                              (3, 3, 64, 64), 1, 1)
    th = ops.row_block(xk, wk, padding=0)
    assert got == pytest.approx(0.75 * 224 / (-(-224 // th) * th))
    assert 0.74 < got <= 0.75
    # C = 32 folds 4 columns: 5/8 of the folded weights are zero taps
    assert route.tile_util_conv2d((32, 56, 56, 32), (3, 3, 32, 32),
                                     padding=1) == pytest.approx(
        3 / 8 * 56 / 64)
