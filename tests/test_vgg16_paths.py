"""The kernel paths VGG-16 runs, in interpret mode, against ``kernels/ref.py``.

* ``conv2d`` in row blocks with a halo: 20x24 planes in blocks of 4 rows
  (5 blocks), 7 rows (a last block of 6, computed on zero rows and cut) and
  the whole plane, with each epilogue the models fuse (the test steers
  ``row_block``, the one place that chooses the rows).
* the weight-stationary GEMM with a reduction axis over C, at a C that is
  not a multiple of its block (fc6's shape, scaled down).
* the unit-stride im2col route of a 3-channel 3x3 (VGG-16's conv1_1).
* VGG-16 itself at width 1/16 on 32x32 images, against the benchmark's
  plain reference (``bench/configs/vgg16.py``).

Tolerances: the kernels accumulate fp32 in another order than XLA's
convolution, so 1e-4 absolute on outputs of order 1-10 (about 10 ulps of the
largest); the whole network, 2e-6 relative to the largest logit, 4x the
largest reading on three seeds (4.7e-7) and 5x below the control's smallest
(the reference at three bf16 passes, 9.7e-6).
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import route
from repro.core.fuse import Epilogue
from repro.kernels import matmul_weight_stationary, ops, ref
from repro.kernels.conv2d import conv2d, row_block
from repro.kernels.matmul import ws_blocks
from repro.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def _epilogue(kind, k, out_shape, key):
    ks = jax.random.split(key, 3)
    if kind == "none":
        return {}
    if kind == "bias+relu":
        return dict(bias=jax.random.normal(ks[0], (k,)), relu=True)
    return dict(scale=jax.random.uniform(ks[0], (k,), minval=0.5, maxval=1.5),
                bias=jax.random.normal(ks[1], (k,)), relu=True,
                residual=jax.random.normal(ks[2], out_shape))


@pytest.mark.parametrize("epilogue", ["none", "bias+relu",
                                      "scale+bias+residual+relu"])
@pytest.mark.parametrize("th", [4, 7, 20])
def test_conv2d_in_row_blocks_matches_reference(th, epilogue, monkeypatch):
    monkeypatch.setattr(sys.modules["repro.kernels.conv2d"], "row_block",
                        lambda *args, **kw: th)
    key = jax.random.PRNGKey(th)
    x = jax.random.normal(key, (2, 20, 24, 5))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 5, 6))
    ep = _epilogue(epilogue, 6, (2, 20, 24, 6), jax.random.fold_in(key, 2))
    got = conv2d(x, w, padding=1, interpret=True, **ep)
    want = ref.conv2d_ref(x, w, padding=1, **ep)
    assert got.shape == want.shape == (2, 20, 24, 6)
    assert _err(got, want) < 1e-4


def test_row_blocks_take_the_whole_channel_axis(monkeypatch):
    """A plane in row blocks reads its whole C in one channel block, whatever
    ``bc`` asks for, so ``tile_util`` counts one block of 128 lanes for C
    and one for K, not three blocks of ``bc = 8``."""
    monkeypatch.setattr(sys.modules["repro.kernels.conv2d"], "row_block",
                        lambda *args, **kw: 6)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (1, 20, 24, 20))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 20, 6))
    ep = _epilogue("scale+bias+residual+relu", 6, (1, 20, 24, 6),
                   jax.random.fold_in(key, 2))
    got = conv2d(x, w, padding=1, bc=8, interpret=True, **ep)
    assert _err(got, ref.conv2d_ref(x, w, padding=1, **ep)) < 1e-4
    assert route.tile_util_conv2d(x.shape, w.shape, padding=1) \
        == pytest.approx(20 * 6 / (128 * 128) * 20 / 24)  # 4 padded rows


def test_row_blocks_split_vgg16s_large_planes_and_bound_the_halo():
    """The whole plane is one block where it fits; VGG-16's 224x224 and
    112x112 layers split into blocks a few rows apart at most."""
    assert row_block((1, 14, 14, 512), (3, 3, 512, 512), padding=1) == 14
    for h, c, k in [(224, 64, 64), (112, 64, 128), (112, 128, 128)]:
        th = row_block((1, h, h, c), (3, 3, c, k), padding=1)
        n = -(-h // th)
        assert 1 < n and n * th - h < n, (h, c, k, th)


def test_row_block_span_counts_the_halo_bytes(monkeypatch):
    """An eager dispatch in row blocks records them, and each block past the
    first re-reads FH - 1 padded rows of the input the kernel reads: 32
    channels fold 4 columns into 128 lanes, so a row is 11 groups of 128
    (40 columns in 10 groups padded to 16, and one more for the taps)."""
    monkeypatch.setattr(sys.modules["repro.kernels.conv2d"], "VMEM_BUDGET",
                        2**20)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 40, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 3, 32, 16))
    xk, wk, pk = route.conv2d_kernel_shapes(x.shape, w.shape, 1, 1)
    assert xk[2:] == (17, 128) and pk == 0
    th = row_block(xk, wk, padding=pk)
    n_r = -(-40 // th)
    assert n_r > 1
    with trace.capture() as tr:
        got = ops.conv2d(x, w, padding=1, impl="pallas")
    (sp,) = tr.spans
    assert sp.attrs["kernel"] == "conv2d" and sp.attrs["row_blocks"] == n_r
    assert sp.attrs["col_fold"] == 4
    plain = 4 * (x.size + w.size + got.size)
    assert sp.attrs["bytes_touched"] - plain == 4 * (n_r - 1) * 2 * 17 * 128
    assert _err(got, ref.conv2d_ref(x, w, padding=1)) < 1e-4


@pytest.mark.parametrize("epilogue", ["none", "bias+relu",
                                      "scale+bias+residual+relu"])
def test_weight_stationary_gemm_in_c_blocks_matches_reference(epilogue,
                                                             monkeypatch):
    # a 64 KiB budget: C = 300 in blocks of 128, the last padded
    monkeypatch.setattr(sys.modules["repro.kernels.matmul"], "WS_BUDGET",
                        2**16)
    assert ws_blocks(300, 40) == (40, 128)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (3, 300))
    w = jax.random.normal(jax.random.fold_in(key, 1), (300, 40))
    ep = _epilogue(epilogue, 40, (3, 40), jax.random.fold_in(key, 2))
    got = matmul_weight_stationary(x, w, interpret=True, **ep)
    assert _err(got, ref.matmul_ref(x, w, **ep)) < 1e-4


def test_c_blocks_stream_whole_rows_only_where_c_does_not_fit():
    assert ws_blocks(2048, 512) == (128, 2048)     # ResNet-50 conv5: one block
    assert ws_blocks(25088, 4096) == (4096, 256)   # fc6: 98 blocks of 4 MiB
    assert ws_blocks(4096, 1000) == (1000, 1024)   # fc8: no padded copy of K
    assert ws_blocks(300, 40, bk=8) == (8, 300)    # a given bk is kept


def test_conv1x1_span_records_c_blocks(monkeypatch):
    monkeypatch.setattr(sys.modules["repro.kernels.matmul"], "WS_BUDGET",
                        2**20)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 1, 1, 2048))
    w = jax.random.normal(jax.random.PRNGKey(9), (2048, 256))
    with trace.capture() as tr:
        got = ops.conv1x1(x, w, impl="pallas",
                          epilogue=Epilogue(bias=jnp.ones((256,))))
    (sp,) = tr.spans
    assert sp.attrs["stationarity"] == "weight_stationary"
    assert sp.attrs["c_blocks"] == 2048 // ws_blocks(2048, 256)[1] > 1
    want = ref.matmul_ref(x.reshape(1, -1), w, bias=jnp.ones((256,)))
    assert _err(got.reshape(1, -1), want) < 1e-3


def test_three_channel_3x3_runs_as_an_im2col_gemm():
    """VGG-16's conv1_1: 3x3 taps of 3 channels are 27 patch columns, one
    lane tile, so the unit-stride conv runs as a GEMM; 16 channels (144
    columns) stay on the conv2d kernel."""
    assert route.runs_as_gemm((3, 3, 3, 64), 1)
    assert not route.runs_as_gemm((3, 3, 16, 64), 1)
    assert not route.runs_as_gemm((1, 1, 3, 64), 1)
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (2, 20, 24, 3))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 3, 8))
    ep = Epilogue(bias=jax.random.normal(key, (8,)), relu=True)
    with trace.capture() as tr:
        got = ops.conv2d(x, w, padding=1, impl="pallas", epilogue=ep)
    (sp,) = tr.spans
    assert sp.attrs["kernel"] == "im2col_gemm" and "row_blocks" not in sp.attrs
    assert route.conv2d_gemm_shape(x.shape, w.shape, 1, 1) == (960, 27, 8)
    want = ref.conv2d_ref(x, w, padding=1, bias=ep.bias, relu=True)
    assert _err(got, want) < 1e-4


@pytest.mark.parametrize("seed", [0, 1])
def test_vgg16_pallas_forward_matches_the_benchmark_reference(seed):
    sys.path.insert(0, ROOT)
    from bench import harness
    from repro.models.cnn import vgg16_apply, vgg16_init
    cell = harness.load_cell("vgg16.single_stream")
    cfg = dict(cell.config, image_size=32)
    params = cell.model.build(cfg, jax.random.PRNGKey(seed), 1 / 16)
    # the program's own init makes the same tree
    mine = vgg16_init(jax.random.PRNGKey(seed), width=1 / 16, image_size=32)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(mine)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (2, 32, 32, 3))
    got = jax.jit(lambda p, x: vgg16_apply(p, x, impl="pallas"))(params, x)
    with jax.default_matmul_precision("highest"):
        want = cell.model.reference(cfg, params, x)
    assert got.shape == want.shape == (2, 1000)
    assert _err(got, want) / float(jnp.max(jnp.abs(want))) < 2e-6
