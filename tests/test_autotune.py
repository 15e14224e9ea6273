"""Tuning cache: keys, candidates, persistence, invalidation, plan overrides.

Every test isolates the cache behind tmp dirs (``REPRO_TUNED_TABLES_DIR`` /
``REPRO_AUTOTUNE_CACHE``) and restores the global enable flag, so the suite
never sees the repo's committed tables or the developer's user cache.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune, carla, plan_conv
from repro.core.autotune import (
    DEFAULT_CONV2D,
    DEFAULT_GEMM,
    Entry,
    TileConfig,
    conv2d_key,
    gemm_key,
    kernel_signature_hash,
)
from repro.core.modes import Dataflow
import importlib

from repro.kernels import ops, ref

# the package exports same-named *functions*, shadowing the submodules
conv2d_mod = importlib.import_module("repro.kernels.conv2d")
matmul_mod = importlib.import_module("repro.kernels.matmul")
from repro.observability import trace


@pytest.fixture
def iso(tmp_path, monkeypatch):
    """Isolated cache dirs + clean in-memory state + restored enable flag."""
    tables = tmp_path / "tables"
    cache = tmp_path / "cache"
    tables.mkdir()
    cache.mkdir()
    monkeypatch.setenv("REPRO_TUNED_TABLES_DIR", str(tables))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    was = autotune.enabled()
    autotune.reset()
    yield {"tables": tables, "cache": cache}
    autotune.reset()
    (autotune.enable if was else autotune.disable)()


def _write_table(path, entries, *, kernel_hash=None, backend=None):
    doc = {
        "version": 1,
        "backend": backend or jax.default_backend(),
        "impl": "pallas",
        "kernel_hash": kernel_hash or kernel_signature_hash(),
        "entries": {k: {"config": cfg.to_dict()} for k, cfg in entries.items()},
    }
    path.write_text(json.dumps(doc))


# ----------------------------- keys + config ---------------------------------
def test_key_formats_are_stable():
    assert (conv2d_key((1, 14, 14, 8), (3, 3, 8, 16), 1, 1, "float32")
            == "conv2d|x1x14x14x8|f3x3x16|s1p1|float32|ep:none")
    assert (gemm_key(784, 16, 8, "float32", "bias+relu")
            == "gemm|m784|c16|k8|float32|ep:bias+relu")


def test_tileconfig_roundtrip_and_labels():
    cfg = TileConfig(bm=64, bk=128, bc=256, stationarity="activation_stationary")
    assert TileConfig.from_dict(cfg.to_dict()) == cfg
    assert cfg.short == "bm64/bk128/bc256/as"
    assert TileConfig(bk=8, stationarity="weight_stationary").short == "bk8/ws"
    assert TileConfig().short == "default"
    hash(cfg)  # must ride through jax.jit as a static argument


def test_defaults_mirror_kernel_constants():
    """core.autotune cannot import the kernels (cycle); enforce sync here."""
    assert (DEFAULT_GEMM.bm, DEFAULT_GEMM.bk, DEFAULT_GEMM.bc) == (
        matmul_mod.BM, matmul_mod.BK, matmul_mod.BC)
    assert (DEFAULT_CONV2D.bk, DEFAULT_CONV2D.bc) == (
        conv2d_mod.BK, conv2d_mod.BC)


def test_kernel_signature_hash_shape():
    h = kernel_signature_hash()
    assert len(h) == 12 and int(h, 16) >= 0
    assert h == kernel_signature_hash()


# ------------------------------ candidates -----------------------------------
def test_conv2d_candidates_include_defaults_and_clamp():
    cands = autotune.conv2d_candidates((1, 14, 14, 8), (3, 3, 8, 16),
                                       stride=1, padding=1, max_candidates=6)
    assert len(cands) <= 6
    # the (clamped) kernel defaults are always in the pool
    assert TileConfig(bk=min(DEFAULT_CONV2D.bk, 16),
                      bc=min(DEFAULT_CONV2D.bc, 8)) in cands
    for c in cands:
        assert 1 <= c.bk <= 16 and 1 <= c.bc <= 8


def test_gemm_candidates_cover_both_stationarities():
    for m in (49, 784):   # below and above the analytic M=128 threshold
        cands = autotune.gemm_candidates(m, 64, 256, max_candidates=8)
        st = {c.stationarity for c in cands}
        assert st == {"weight_stationary", "activation_stationary"}, (m, st)
        # the analytic rule's pick sorts first (budget-truncation safety)
        expected_first = "weight_stationary" if m < 128 \
            else "activation_stationary"
        assert cands[0].stationarity == expected_first
        for c in cands:
            if c.bm is not None:
                assert c.bm <= m


@pytest.mark.parametrize("batch", [1, 8])
def test_stem_gemm_candidates_are_lane_legal(batch):
    """ResNet-50's 7x7/2 stem runs as a (B*12544, 192) @ (192, 64) GEMM: its
    7x7 filter padded with zero taps to 8x8, folded by space-to-depth into
    4x4 taps of 12 channels.  On a TPU its channel tiles must be whole axes
    or 128-lane multiples, so no candidate gets a bc below 128 (the old
    conv2d entry had bc=3)."""
    m, c, k = autotune.conv2d_gemm_shape((batch, 224, 224, 3), (7, 7, 3, 64),
                                         2, 3)
    assert (m, c, k) == (batch * 112 * 112, 192, 64)
    cands = autotune.gemm_candidates(m, c, k, max_candidates=64)
    assert {t.stationarity for t in cands} == {"weight_stationary",
                                               "activation_stationary"}
    for t in cands:
        assert t.bk == k or t.bk % 128 == 0, t
        if t.bc is not None:
            assert t.bc >= 128 and (t.bc == c or t.bc % 128 == 0), t


def test_conv2d_candidates_are_lane_legal():
    cands = autotune.conv2d_candidates((1, 56, 56, 256), (3, 3, 256, 64),
                                       stride=1, padding=1, max_candidates=64)
    assert cands
    for t in cands:
        assert t.bk == 64 and t.bc in (128, 256), t


def test_strided_conv_tunes_as_its_im2col_gemm(iso):
    """A strided conv's tiles are GEMM tiles: an entry under its conv2d key
    is never read, the entry under its GEMM key is, and tile_util describes
    the GEMM that ran, its zero taps (147 of 192 columns are real) counted
    as padding."""
    autotune.enable()
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (1, 16, 16, 3))
    w = jax.random.normal(jax.random.fold_in(key, 1), (7, 7, 3, 8))
    m, c, k = autotune.conv2d_gemm_shape(x.shape, w.shape, 2, 3)
    real = 7 * 7 * 3 / c
    autotune.put(conv2d_key(x.shape, w.shape, 2, 3, x.dtype),
                 TileConfig(bk=8, bc=3))
    with trace.capture() as tr:
        carla.carla_conv(x, w, stride=2, padding=3, impl="pallas")
    (ksp,) = tr.spans[0].children
    assert ksp.attrs["tuned"] is False
    assert ksp.attrs["kernel"] == "im2col_gemm"
    assert ksp.attrs["tile_util"] == pytest.approx(
        real * autotune.tile_util_gemm(
            m, c, k, stationarity=ksp.attrs["stationarity"]))

    tiles = TileConfig(bm=32, bk=8, bc=c, stationarity="activation_stationary")
    autotune.put(gemm_key(m, c, k, x.dtype), tiles)
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, stride=2, padding=3, impl="pallas")
    sp = tr.spans[0]
    (ksp,) = sp.children
    for s in (sp, ksp):
        assert s.attrs["tuned"] is True
        assert s.attrs["tile_config"] == tiles.short
        assert s.attrs["tile_util"] == pytest.approx(
            real * autotune.tile_util_gemm(m, c, k, tiles))
    assert ksp.attrs["stationarity"] == "activation_stationary"
    want = ref.conv2d_ref(x, w, stride=2, padding=3)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-3


# --------------------------- cache + persistence ------------------------------
def test_lookup_precedence_table_cache_runtime(iso):
    key = gemm_key(100, 64, 32, "float32")
    _write_table(iso["tables"] / "net.json", {key: TileConfig(bk=32)})
    autotune.reset()
    assert autotune.lookup(key).source == "table"
    assert autotune.lookup(key).config == TileConfig(bk=32)

    backend = jax.default_backend()
    _write_table(iso["cache"] / f"cache.{backend}.json",
                 {key: TileConfig(bk=64)})
    autotune.reset()
    assert autotune.lookup(key).source == "cache"
    assert autotune.lookup(key).config == TileConfig(bk=64)

    autotune.put(key, TileConfig(bk=128))
    assert autotune.lookup(key).source == "runtime"
    assert autotune.lookup(key).config == TileConfig(bk=128)


def test_epilogue_fallback_lookup(iso):
    base = gemm_key(100, 64, 32, "float32")
    autotune.put(base, TileConfig(bk=16))
    # a fused dispatch falls back to the ep:none entry...
    assert autotune.lookup(gemm_key(100, 64, 32, "float32",
                                    "scale+bias+relu")).config.bk == 16
    # ...unless an exact fused entry exists
    autotune.put(gemm_key(100, 64, 32, "float32", "scale+bias+relu"),
                 TileConfig(bk=8))
    assert autotune.lookup(gemm_key(100, 64, 32, "float32",
                                    "scale+bias+relu")).config.bk == 8
    # and a different shape stays a miss
    assert autotune.lookup(gemm_key(101, 64, 32, "float32")) is None


def test_stale_table_rejected_and_reported(iso):
    key = gemm_key(100, 64, 32, "float32")
    _write_table(iso["tables"] / "old.json", {key: TileConfig(bk=32)},
                 kernel_hash="deadbeef0000")
    autotune.reset()
    assert autotune.lookup(key) is None
    (stale,) = autotune.stale_tables()
    assert stale["table_hash"] == "deadbeef0000"
    assert stale["current_hash"] == kernel_signature_hash()
    assert stale["path"].endswith("old.json")


def test_wrong_backend_table_skipped_silently(iso):
    key = gemm_key(100, 64, 32, "float32")
    _write_table(iso["tables"] / "tpu.json", {key: TileConfig(bk=32)},
                 backend="tpu-v9000")
    autotune.reset()
    assert autotune.lookup(key) is None
    assert autotune.stale_tables() == []   # wrong backend is not "stale"


def test_save_user_cache_merges(iso):
    k1 = gemm_key(10, 8, 8, "float32")
    k2 = gemm_key(20, 8, 8, "float32")
    autotune.save_user_cache({k1: Entry(TileConfig(bk=8))})
    autotune.save_user_cache({k2: Entry(TileConfig(bk=4))})
    autotune.reset()
    assert autotune.lookup(k1).config.bk == 8
    assert autotune.lookup(k2).config.bk == 4


# ------------------------------- tile_util ------------------------------------
def test_tile_util_math():
    # conv2d: cin=24 -> bc=128 clamps to 24, k=16 -> bk=16 (9 * 24 = 216
    # columns: too wide for the im2col route; 24 does not divide 128, so no
    # fold): each block takes 128 of the MXU's lanes
    assert autotune.tile_util_conv2d((1, 14, 14, 24), (3, 3, 24, 16)) \
        == pytest.approx((24 * 16) / (128 * 128))
    # odd tiles pad: cin=24 over bc=3 -> 8 blocks, k=16 over bk=5 -> 4
    # blocks, 128 lanes each
    got = autotune.tile_util_conv2d((1, 14, 14, 24), (3, 3, 24, 16),
                                    TileConfig(bk=5, bc=3))
    assert got == pytest.approx((24 * 16) / (8 * 128 * 4 * 128))
    # cin=16 folds 8 columns into 128 lanes: 2 column taps of 8 columns
    # hold the 3 taps (zero taps), and 12 columns take 2 groups, padded to 8
    assert autotune.tile_util_conv2d((1, 14, 14, 16), (3, 3, 16, 16)) \
        == pytest.approx((9 * 16 * 16 * 12 * 12) / (3 * 2 * 128 * 128 * 12 * 8))
    # gemm WS: only K pads
    assert autotune.tile_util_gemm(
        7, 64, 30, TileConfig(bk=8, stationarity="weight_stationary")
    ) == pytest.approx(30 / 32)
    # gemm AS: M and K pad; bc=64 clamps to C=60 so C does not
    assert autotune.tile_util_gemm(
        100, 60, 30, TileConfig(bm=64, bk=16, bc=64,
                                stationarity="activation_stationary")
    ) == pytest.approx((100 * 30) / (128 * 32))


# ------------------------- dispatch + plan integration ------------------------
def test_disabled_cache_never_consulted(iso):
    key = gemm_key(4 * 7 * 7, 8, 16, "float32")
    autotune.put(key, TileConfig(bk=4, stationarity="weight_stationary"))
    autotune.disable()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 7, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 8, 16))
    with trace.capture() as tr:
        carla.carla_conv(x, w)
    sp = tr.spans[0]
    assert sp.attrs["tuned"] is False
    assert sp.attrs["tile_config"] == "default"
    assert sp.attrs["tuning_source"] == "analytic"


def test_plan_conv_tuned_stationarity_flips_effective_dataflow(iso):
    autotune.enable()
    x_shape, w_shape = (1, 28, 28, 8), (1, 1, 8, 16)
    rows = 28 * 28
    plan = plan_conv(x_shape, w_shape)
    assert plan.dataflow == Dataflow.CONV1X1_FEATURE_STATIONARY
    assert plan.tile_config is None and plan.tuning_source == "analytic"

    autotune.put(gemm_key(rows, 8, 16, "float32"),
                 TileConfig(bk=8, stationarity="weight_stationary"))
    plan = plan_conv(x_shape, w_shape)
    # the analytic ledger is unchanged; only the effective dataflow moves
    assert plan.dataflow == Dataflow.CONV1X1_FEATURE_STATIONARY
    assert plan.effective_dataflow == Dataflow.CONV1X1_WEIGHT_STATIONARY
    assert plan.tuning_source == "runtime"


def test_tuned_conv2d_dispatch_matches_ref_and_records_span(iso):
    autotune.enable()
    key = jax.random.PRNGKey(2)
    # 24 channels: 216 patch columns, too wide for the im2col route, and
    # not a divisor of 128 lanes, so the conv2d kernel runs it unfolded
    x = jax.random.normal(key, (1, 10, 10, 24))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 24, 16))
    autotune.put(conv2d_key(x.shape, w.shape, 1, 1, x.dtype),
                 TileConfig(bk=5, bc=3))
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, padding=1, impl="pallas")
    want = ref.conv2d_ref(x, w, stride=1, padding=1)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-3
    sp = tr.spans[0]
    assert sp.attrs["tuned"] is True
    assert sp.attrs["tile_config"] == "bk5/bc3"
    assert sp.attrs["tuning_source"] == "runtime"
    assert sp.attrs["tile_util"] == pytest.approx(
        (24 * 16) / (8 * 128 * 4 * 128))
    # the kernel child span carries the same tuning ledger
    (ksp,) = sp.children
    assert ksp.attrs["tile_config"] == "bk5/bc3"
    assert ksp.attrs["tile_util"] == sp.attrs["tile_util"]


def test_repro_impl_env_overrides_dispatch(iso, monkeypatch):
    """Satellite: REPRO_IMPL forces the engine and the span records it."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 4))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 4, 8))
    monkeypatch.setenv("REPRO_IMPL", "pallas")
    with trace.capture() as tr:
        out_p = ops.conv2d(x, w, padding=1, impl="ref")   # env wins
    assert tr.spans[0].attrs["impl"] == "pallas"
    monkeypatch.setenv("REPRO_IMPL", "ref")
    with trace.capture() as tr:
        out_r = ops.conv2d(x, w, padding=1, impl="pallas")
    assert tr.spans[0].attrs["impl"] == "ref"
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 1e-4
    monkeypatch.delenv("REPRO_IMPL")
    assert ops._resolve("auto") in ("pallas", "ref")
