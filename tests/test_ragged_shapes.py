"""Ragged-shape parity: dims that are NOT tile multiples, across everything.

The tuner's candidate pool includes tiles that leave remainders on every axis
(M, C, K), so the padding/clamping paths in ``conv2d.py``/``matmul.py`` must
be exact for arbitrary (dim, tile) combinations — not just the MXU-aligned
shapes the defaults were written for.  This sweeps prime-ish dims through all
four dataflows x {pallas, ref} x {unfused, fused epilogue}, both by calling
the kernels with explicitly odd tiles and by dispatching through
``carla_conv`` with odd tiles injected via the tuning cache.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import Epilogue, autotune, carla_conv
from repro.core.autotune import (
    TileConfig,
    conv2d_gemm_shape,
    conv2d_key,
    gemm_key,
)
from repro.kernels import (
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


@pytest.fixture
def iso_cache(tmp_path, monkeypatch):
    """Tuning cache isolated from the repo's committed tables and enabled."""
    monkeypatch.setenv("REPRO_TUNED_TABLES_DIR", str(tmp_path / "t"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c"))
    was = autotune.enabled()
    autotune.reset()
    autotune.enable()
    yield
    autotune.reset()
    (autotune.enable if was else autotune.disable)()


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
    # through the pallas dispatch with explicit tiles: unit stride runs the
    # conv2d kernel, stride 2 the im2col GEMM (bc tiles its reduction)
    got = ops._conv2d_jit(x, w, kw.get("scale"), kw.get("bias"),
                          kw.get("residual"), relu=bool(kw.get("relu")),
                          stride=s, padding=p, impl="pallas",
                          tiles=TileConfig(bk=bk, bc=bc))
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


# ----------------- full dispatch with injected odd tiles ----------------------
# One case per paper dataflow; the cache entry forces ragged tiles (and, for
# the 1x1s, swaps the stationarity away from the analytic rule).
DATAFLOW_RAGGED = [
    ("3x3", dict(h=9, c=37, k=53, fl=3, s=1, p=1),
     TileConfig(bk=7, bc=5)),
    ("7x7", dict(h=15, c=3, k=21, fl=7, s=2, p=3),
     TileConfig(bk=4, bc=2)),
    # 1x1 feature-stationary shape (M=81 < 128 rule says WS; force AS)
    ("1x1_as", dict(h=9, c=37, k=53, fl=1, s=1, p=0),
     TileConfig(bm=13, bk=7, bc=11, stationarity="activation_stationary")),
    # 1x1 weight-stationary override at large M (the empirical flip)
    ("1x1_ws", dict(h=13, c=37, k=53, fl=1, s=1, p=0),
     TileConfig(bk=7, stationarity="weight_stationary")),
]


@pytest.mark.parametrize("tag,case,tiles",
                         DATAFLOW_RAGGED, ids=[t[0] for t in DATAFLOW_RAGGED])
@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("fused", [False, True])
def test_carla_conv_ragged_tuned_parity(tag, case, tiles, impl, fused,
                                        iso_cache):
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
    # inject the ragged entry for BOTH the fused and unfused key (the fused
    # lookup would fall back to ep:none anyway; make the exact hit explicit)
    tag_ep = ep.tag if ep is not None else "none"
    if fl == 1:
        cache_key = gemm_key(h * h, c, k, x.dtype, tag_ep)
    elif s > 1:   # runs as an im2col GEMM, keyed by that GEMM's shape
        cache_key = gemm_key(*conv2d_gemm_shape(x.shape, w.shape, s, p),
                             x.dtype, tag_ep)
    else:
        cache_key = conv2d_key(x.shape, w.shape, s, p, x.dtype, tag_ep)
    autotune.put(cache_key, tiles)

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
