"""Per-kernel validation: shape/dtype sweeps + hypothesis, vs ref.py oracles.

Kernels called directly run under interpret=True; conv2d runs through
``kernels.ops`` (which interprets off a TPU), so its strided cases cover the
im2col GEMM route.  Tolerances: fp32 1e-4 relative-ish; bf16 inputs 2e-2.

``hypothesis`` is optional: the randomized any-(m,c,k) matmul property has a
deterministic pinned-shape twin that always runs.
"""
import jax
import jax.numpy as jnp
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.modes import Stationarity
from repro.kernels import (
    conv1d_causal,
    matmul_act_stationary,
    matmul_weight_stationary,
    ops,
    ref,
)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, dtype)


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))


def _tol(dtype, scale=1.0):
    return (2e-2 if dtype == jnp.bfloat16 else 2e-4) * scale


# ------------------------------- conv2d --------------------------------------
CONV_CASES = [
    # (b, h, w, c, k, fl, stride, pad)
    (1, 8, 8, 4, 8, 3, 1, 1),
    (2, 14, 14, 16, 32, 3, 1, 1),
    (1, 16, 16, 8, 8, 3, 2, 1),
    (1, 15, 15, 7, 5, 3, 1, 1),      # odd sizes
    (1, 28, 28, 3, 16, 7, 2, 3),     # ResNet conv1 pattern
    (1, 9, 9, 3, 4, 5, 1, 2),        # 5x5
    (2, 8, 8, 130, 130, 3, 1, 1),    # > one channel tile
]


@pytest.mark.parametrize("b,h,w,c,k,fl,s,p", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_sweep(b, h, w, c, k, fl, s, p, dtype):
    key = jax.random.PRNGKey(b * 100 + h + c + fl)
    x = _rand(key, (b, h, w, c), dtype)
    wgt = _rand(jax.random.fold_in(key, 1), (fl, fl, c, k), dtype)
    got = ops.conv2d(x, wgt, stride=s, padding=p, impl="pallas")
    want = ref.conv2d_ref(x, wgt, stride=s, padding=p)
    assert got.shape == want.shape
    assert _err(got, want) < _tol(dtype, scale=fl * fl * c ** 0.5)


# ------------------------------- matmul --------------------------------------
MM_CASES = [(128, 256, 128), (100, 300, 80), (256, 512, 384), (1, 512, 300),
            (4, 4096, 128), (513, 129, 257)]


@pytest.mark.parametrize("m,c,k", MM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_act_stationary_sweep(m, c, k, dtype):
    key = jax.random.PRNGKey(m + c + k)
    x = _rand(key, (m, c), dtype)
    w = _rand(jax.random.fold_in(key, 1), (c, k), dtype)
    got = matmul_act_stationary(x, w, interpret=True)
    want = ref.matmul_ref(x, w).astype(dtype)
    assert got.shape == (m, k)
    assert _err(got, want) < _tol(dtype, scale=c ** 0.5)


@pytest.mark.parametrize("m,c,k", [(1, 256, 128), (4, 512, 300), (8, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_weight_stationary_sweep(m, c, k, dtype):
    key = jax.random.PRNGKey(m * 7 + c + k)
    x = _rand(key, (m, c), dtype)
    w = _rand(jax.random.fold_in(key, 1), (c, k), dtype)
    got = matmul_weight_stationary(x, w, interpret=True)
    want = ref.matmul_ref(x, w).astype(dtype)
    assert _err(got, want) < _tol(dtype, scale=c ** 0.5)


def _check_matmul_property(m, c, k):
    """Any (m, c, k) — padding/tiling must never change the math."""
    key = jax.random.PRNGKey(m * 90001 + c * 31 + k)
    x = _rand(key, (m, c), jnp.float32)
    w = _rand(jax.random.fold_in(key, 1), (c, k), jnp.float32)
    want = ref.matmul_ref(x, w)
    assert _err(matmul_act_stationary(x, w, interpret=True),
                want) < 1e-3 * c ** 0.5
    assert _err(matmul_weight_stationary(x, w, interpret=True),
                want) < 1e-3 * c ** 0.5


# Deterministic twin of the hypothesis property: primes, 1s, tile edges
# (127/128/129), and ragged combinations — the shapes shrinking always finds.
MM_PROPERTY_CASES = [
    (1, 1, 1), (1, 300, 1), (300, 1, 300), (2, 3, 5),
    (127, 128, 129), (128, 127, 126), (129, 129, 129),
    (31, 257, 63), (200, 100, 300), (97, 193, 89),
]


@pytest.mark.parametrize("m,c,k", MM_PROPERTY_CASES)
def test_matmul_property_grid(m, c, k):
    _check_matmul_property(m, c, k)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(m=st.integers(1, 300), c=st.integers(1, 300), k=st.integers(1, 300))
    def test_matmul_property(m, c, k):
        _check_matmul_property(m, c, k)


def test_stationarity_dispatch():
    """The planner mirrors the paper: small fmaps -> weight-stationary."""
    from repro.core.modes import select_stationarity
    assert select_stationarity(4) == Stationarity.WEIGHT_STATIONARY
    assert select_stationarity(4096) == Stationarity.ACTIVATION_STATIONARY


# ------------------------------- conv1d --------------------------------------
@pytest.mark.parametrize("b,t,c,fl", [(1, 16, 32, 4), (2, 33, 96, 4),
                                      (2, 64, 513, 2), (1, 8, 8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_sweep(b, t, c, fl, dtype):
    key = jax.random.PRNGKey(b + t + c + fl)
    x = _rand(key, (b, t, c), dtype)
    w = _rand(jax.random.fold_in(key, 1), (fl, c), dtype)
    got = conv1d_causal(x, w, interpret=True)
    want = ref.conv1d_causal_ref(x, w)
    assert _err(got, want) < _tol(dtype, scale=fl)


# -------------------------- fused decode attention ---------------------------
def _decode_ref(q, ck, cv, pos):
    b, h, dh = q.shape
    kh = ck.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, dh).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bskd->bkgs", qg, ck.astype(jnp.float32)) * dh ** -0.5
    kpos = jnp.arange(ck.shape[1])[None, None, None]
    sc = jnp.where(kpos <= pos[:, None, None, None], sc, -2.38e38)
    w = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", w,
                      cv.astype(jnp.float32)).reshape(b, h, dh)


@pytest.mark.parametrize("b,s,h,kh,dh,bs", [
    (2, 256, 8, 2, 32, 64), (1, 1000, 4, 4, 64, 256), (2, 64, 6, 3, 16, 64)])
def test_decode_attention_sweep(b, s, h, kh, dh, bs):
    from repro.kernels import decode_attention
    key = jax.random.PRNGKey(s + h)
    q = _rand(key, (b, h, dh), jnp.float32)
    ck = _rand(jax.random.fold_in(key, 1), (b, s, kh, dh), jnp.float32)
    cv = _rand(jax.random.fold_in(key, 2), (b, s, kh, dh), jnp.float32)
    pos = jnp.arange(b, dtype=jnp.int32) * (s // 2) + s // 3
    got = decode_attention(q, ck, cv, pos, bs=bs, interpret=True)
    assert _err(got, _decode_ref(q, ck, cv, pos)) < 1e-4


# --------------------------- fused flash attention ----------------------------
@pytest.mark.parametrize("b,t,h,kh,dh,win,cap", [
    (1, 512, 4, 2, 32, 0, 0.0), (2, 512, 8, 4, 64, 128, 0.0),
    (1, 1024, 4, 2, 32, 0, 30.0), (1, 256, 6, 3, 16, 0, 0.0)])
def test_flash_fused_sweep(b, t, h, kh, dh, win, cap):
    from repro import perf
    from repro.kernels.flash_attention import flash_attention_fused
    from repro.models.attention import (
        NEG_INF,
        _causal_window_mask,
        _gqa_out,
        _gqa_scores,
    )
    key = jax.random.PRNGKey(t + h)
    q = _rand(key, (b, t, h, dh), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (b, t, kh, dh), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (b, t, kh, dh), jnp.float32)
    got = flash_attention_fused(q, k, v, window=win, softcap=cap,
                                bq=128, bk=128, interpret=True)
    with perf.baseline():
        sc = _gqa_scores(q, k)
        if cap:
            sc = cap * jnp.tanh(sc / cap)
        m = _causal_window_mask(t, t, 0, win)
        sc = jnp.where(m[None, None, None], sc, NEG_INF)
        want = _gqa_out(sc, v, jnp.float32)
    assert _err(got, want) < 1e-4
