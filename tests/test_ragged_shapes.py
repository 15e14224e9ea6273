"""Ragged-shape parity: dims that are NOT tile multiples, across everything.

The kernels take explicit tiles that leave remainders on every axis (M, C,
K), so the padding/clamping paths in ``conv2d.py``/``matmul.py`` must be
exact for arbitrary (dim, tile) combinations — not just the MXU-aligned
shapes of the kernels' own blocks.  This sweeps prime-ish dims through the
kernels with explicitly odd tiles, and through all four dataflows x
{pallas, ref} x {unfused, fused epilogue} by dispatching through
``carla_conv`` on the route each shape takes.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import Epilogue, carla_conv
from repro.core.route import route
from repro.kernels import (
    conv2d,
    matmul_act_stationary,
    matmul_weight_stationary,
    ops,
    ref,
)

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))


def _epilogue(k, out_shape, key):
    return Epilogue(
        scale=jax.random.uniform(key, (k,), minval=0.5, maxval=1.5),
        bias=jax.random.normal(jax.random.fold_in(key, 1), (k,)),
        relu=True,
        residual=jax.random.normal(jax.random.fold_in(key, 2), out_shape))


# --------------------- direct kernel calls, odd tiles -------------------------
# C=37, K=53 are prime (never tile multiples); tiles 5/7/11 leave remainders
# on every axis.
RAGGED_CONV = [
    # (h, c, k, fl, stride, pad, bk, bc)
    (9, 37, 53, 3, 1, 1, 7, 5),
    (11, 37, 53, 3, 2, 1, 11, 7),
    (13, 37, 53, 1, 1, 0, 5, 11),
    (15, 37, 53, 7, 2, 3, 53, 37),   # tiles == dims exactly
]


@pytest.mark.parametrize("h,c,k,fl,s,p,bk,bc", RAGGED_CONV)
@pytest.mark.parametrize("fused", [False, True])
def test_conv2d_kernel_ragged_tiles(h, c, k, fl, s, p, bk, bc, fused):
    key = jax.random.PRNGKey(h * 7 + fl)
    x = jax.random.normal(key, (1, h, h, c))
    w = jax.random.normal(jax.random.fold_in(key, 1), (fl, fl, c, k))
    kw = {}
    if fused:
        oh = (h - fl + 2 * p) // s + 1
        ep = _epilogue(k, (1, oh, oh, k), jax.random.fold_in(key, 2))
        kw = dict(scale=ep.scale, bias=ep.bias, relu=True,
                  residual=ep.residual)
    if s == 1:
        # the conv2d kernel itself, with the odd tiles
        got = conv2d(x, w, padding=p, bk=bk, bc=bc, interpret=True, **kw)
    else:
        # a strided conv runs as the im2col GEMM, at the kernel's blocks
        got = ops._conv2d_jit(x, w, kw.get("scale"), kw.get("bias"),
                              kw.get("residual"), relu=bool(kw.get("relu")),
                              stride=s, padding=p, impl="pallas")
    want = ref.conv2d_ref(x, w, stride=s, padding=p, **kw)
    assert got.shape == want.shape
    assert _err(got, want) < 1e-3, (h, c, k, fl, s, bk, bc, fused)


RAGGED_MM = [
    # (m, c, k, bm, bk, bc)
    (97, 37, 53, 13, 7, 11),
    (5, 129, 257, 1, 100, 130),    # tiny M, tiles straddling the dims
    (130, 64, 100, 130, 100, 64),  # tiles == / > dims
]


@pytest.mark.parametrize("m,c,k,bm,bk,bc", RAGGED_MM)
@pytest.mark.parametrize("fused", [False, True])
def test_matmul_ragged_tiles_both_stationarities(m, c, k, bm, bk, bc, fused):
    key = jax.random.PRNGKey(m + c)
    x = jax.random.normal(key, (m, c))
    w = jax.random.normal(jax.random.fold_in(key, 1), (c, k))
    kw = {}
    if fused:
        ep = _epilogue(k, (m, k), jax.random.fold_in(key, 2))
        kw = dict(scale=ep.scale, bias=ep.bias, relu=True,
                  residual=ep.residual)
    want = ref.matmul_ref(x, w, **kw)
    got_as = matmul_act_stationary(x, w, bm=bm, bk=bk, bc=min(bc, c),
                                   interpret=True, **kw)
    got_ws = matmul_weight_stationary(x, w, bk=bk, interpret=True, **kw)
    assert _err(got_as, want) < 1e-3, ("as", m, c, k, bm, bk, bc, fused)
    assert _err(got_ws, want) < 1e-3, ("ws", m, c, k, bk, fused)


# ------------------------ full dispatch, every route -------------------------
# One case per paper dataflow, on the route its shape takes: the conv2d
# kernel, the im2col GEMM, and the 1x1 GEMM in both stationarities (81 rows
# are below the 128 of one MXU tile, 169 above).
DATAFLOW_RAGGED = [
    ("3x3", dict(h=9, c=37, k=53, fl=3, s=1, p=1), "conv2d", None),
    ("7x7", dict(h=15, c=3, k=21, fl=7, s=2, p=3), "im2col_gemm",
     "weight_stationary"),     # 8x8 outputs: 64 rows
    ("1x1_as", dict(h=13, c=37, k=53, fl=1, s=1, p=0), "gemm",
     "activation_stationary"),
    ("1x1_ws", dict(h=9, c=37, k=53, fl=1, s=1, p=0), "gemm",
     "weight_stationary"),
]


@pytest.mark.parametrize("tag,case,kernel,stationarity",
                         DATAFLOW_RAGGED, ids=[t[0] for t in DATAFLOW_RAGGED])
@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("fused", [False, True])
def test_carla_conv_ragged_parity(tag, case, kernel, stationarity, impl,
                                  fused):
    h, c, k = case["h"], case["c"], case["k"]
    fl, s, p = case["fl"], case["s"], case["p"]
    key = jax.random.PRNGKey(sum(map(ord, tag)))
    x = jax.random.normal(key, (1, h, h, c))
    w = jax.random.normal(jax.random.fold_in(key, 1), (fl, fl, c, k))
    ep = None
    kw = {}
    if fused:
        oh = (h - fl + 2 * p) // s + 1
        ep = _epilogue(k, (1, oh, oh, k), jax.random.fold_in(key, 2))
        kw = dict(scale=ep.scale, bias=ep.bias, relu=True,
                  residual=ep.residual)
    r = route(x.shape, w.shape, s, p)
    assert r.kernel == kernel
    assert (r.stationarity and r.stationarity.value) == stationarity

    got = carla_conv(x, w, stride=s, padding=p, impl=impl, epilogue=ep)
    want = ref.conv2d_ref(x, w, stride=s, padding=p, **kw)
    assert got.shape == want.shape
    assert _err(got, want) < 1e-3, (tag, impl, fused)


# ------------------------- randomized ragged property -------------------------
if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(m=st.integers(1, 200), c=st.integers(1, 96), k=st.integers(1, 96),
           bm=st.integers(1, 64), bk=st.integers(1, 64), bc=st.integers(1, 64))
    def test_matmul_any_ragged_tiles(m, c, k, bm, bk, bc):
        key = jax.random.PRNGKey(m * 1000 + c * 10 + k)
        x = jax.random.normal(key, (m, c))
        w = jax.random.normal(jax.random.fold_in(key, 1), (c, k))
        want = ref.matmul_ref(x, w)
        got = matmul_act_stationary(x, w, bm=bm, bk=bk, bc=bc,
                                    interpret=True)
        assert _err(got, want) < 1e-3
else:
    def test_matmul_any_ragged_tiles():
        """Deterministic twin of the hypothesis property."""
        for m, c, k, bm, bk, bc in [(200, 96, 96, 64, 64, 64),
                                    (1, 1, 1, 64, 64, 64),
                                    (31, 17, 19, 3, 5, 7)]:
            key = jax.random.PRNGKey(m)
            x = jax.random.normal(key, (m, c))
            w = jax.random.normal(jax.random.fold_in(key, 1), (c, k))
            got = matmul_act_stationary(x, w, bm=bm, bk=bk, bc=bc,
                                        interpret=True)
            assert _err(got, ref.matmul_ref(x, w)) < 1e-3
