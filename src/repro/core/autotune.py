"""Empirical per-layer tuning cache — tile sizes and dataflow, keyed by shape.

CARLA's controller reconfigures the dataflow per layer so PE utilization stays
near 98% across every shape of ResNet-50/VGG-16 (paper §III).  The software
twin reproduces the *selection rule* analytically (``core.modes``), but the
Pallas kernels additionally have tile-size knobs the ASIC does not
(``bm/bk/bc``), and the best setting is an empirical property of the execution
backend, not of the rule.  This module is the persistence + lookup layer for
an MMIE-style per-layer operating point chosen by measurement:

  * **Key**: ``(op kind, layer shape, dtype, epilogue signature)`` rendered as
    a flat string (backend lives in the table header, not the key).  1x1 convs
    flatten to their GEMM shape so ``conv1x1`` and ``gemm`` share entries,
    and the convs that the Pallas path runs as an im2col GEMM (strided, or
    with a patch row of at most 128 lanes: :func:`runs_as_gemm`) key by that
    GEMM's shape (:func:`conv2d_gemm_shape`), and those that fold output
    columns into the lanes (:func:`col_fold`) by the folded conv's
    (:func:`conv2d_kernel_shapes`): :func:`conv2d_tuning_key`.
  * **Entry**: the winning :class:`TileConfig` — tile sizes plus, for GEMM
    shapes, the stationarity (dataflow) choice itself — with the measured
    tuned/default wall times and where the entry came from (``table`` =
    committed, ``cache`` = user cache dir, ``runtime`` = injected in-process).
  * **Invalidation**: every table records ``kernel_signature_hash()`` — a hash
    of the kernel sources (``conv2d.py``/``matmul.py``).  Entries whose hash
    no longer matches are ignored, and committed tables that went stale fail
    ``benchmarks/check_regression.py``.
  * **Overhead contract**: ``enabled()`` is one module-attribute read (the
    same discipline as ``observability.trace``); a lookup is one or two dict
    hits.  Dispatch sites gate on ``enabled()`` first, so the disabled path
    costs nothing.

The search itself lives in ``benchmarks/autotune.py``; this module only
defines keys, candidate generation (cost-model-seeded), the cache, and the
``tile_util`` padding-waste metric (logical FLOPs / padded FLOPs — the TPU
analogue of the paper's PUF).

Sources, highest precedence first:
  1. runtime entries injected via :func:`put` (tests, notebooks);
  2. the user cache dir (``~/.cache/repro-autotune`` or
     ``$REPRO_AUTOTUNE_CACHE``), written by ``benchmarks/autotune.py``;
  3. committed tables under ``src/repro/kernels/tuned/`` (or
     ``$REPRO_TUNED_TABLES_DIR``), produced with ``--commit``.

Enable with :func:`enable` or ``REPRO_AUTOTUNE=1``.
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass

from .modes import Stationarity, select_stationarity

# ---------------------------------------------------------------------------
# Tile configurations
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TileConfig:
    """One operating point: tile sizes + (for GEMM shapes) the stationarity.

    ``None`` fields mean "keep the kernel's default".  Frozen and hashable so
    a config can ride through ``jax.jit`` as a static argument.
    """

    bm: int | None = None
    bk: int | None = None
    bc: int | None = None
    stationarity: str | None = None   # modes.Stationarity.value, or None

    @property
    def short(self) -> str:
        """Compact span-attribute label, e.g. ``"bm64/bk128/bc256/as"``."""
        parts = [f"{n}{v}" for n, v in
                 (("bm", self.bm), ("bk", self.bk), ("bc", self.bc))
                 if v is not None]
        if self.stationarity:
            parts.append("ws" if self.stationarity == "weight_stationary"
                         else "as")
        return "/".join(parts) if parts else "default"

    def to_dict(self) -> dict:
        return {k: v for k, v in (("bm", self.bm), ("bk", self.bk),
                                  ("bc", self.bc),
                                  ("stationarity", self.stationarity))
                if v is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "TileConfig":
        return cls(bm=d.get("bm"), bk=d.get("bk"), bc=d.get("bc"),
                   stationarity=d.get("stationarity"))


# The kernels' hardcoded constants (kept in sync by tests/test_autotune.py —
# importing the kernels here would cycle through repro.kernels.__init__).
DEFAULT_GEMM = TileConfig(bm=128, bk=128, bc=512)     # matmul.BM/BK/BC
DEFAULT_CONV2D = TileConfig(bk=128, bc=128)           # conv2d.BK/BC
LANES = 128     # the MXU's width, and the lanes a channel block occupies


@dataclass(frozen=True)
class Entry:
    """A cache hit: the winning config and the measurements behind it."""

    config: TileConfig
    source: str = "runtime"        # "table" | "cache" | "runtime"
    tuned_ms: float = 0.0
    default_ms: float = 0.0


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------
def conv2d_key(x_shape, w_shape, stride: int, padding: int, dtype,
               epilogue: str = "none") -> str:
    b, h, w, c = x_shape
    fh, fw, _, k = w_shape
    return (f"conv2d|x{b}x{h}x{w}x{c}|f{fh}x{fw}x{k}|s{stride}p{padding}"
            f"|{dtype}|ep:{epilogue}")


def gemm_key(m: int, c: int, k: int, dtype, epilogue: str = "none") -> str:
    return f"gemm|m{m}|c{c}|k{k}|{dtype}|ep:{epilogue}"


def runs_as_gemm(w_shape, stride: int) -> bool:
    """Whether the Pallas path runs a conv (not a 1x1) as an im2col GEMM:
    a strided one, since Mosaic refuses a strided slice inside the conv2d
    kernel, or a unit-stride one whose patch row ``FH*FW*C`` fits one
    128-lane tile (VGG-16's conv1_1: 27 columns), which the conv2d kernel
    would contract 3 lanes at a time.  The one place that decides it."""
    fh, fw, cin, _ = w_shape
    return stride > 1 or (fh * fw > 1 and fh * fw * cin <= 128)


def col_fold(x_shape, w_shape, stride: int) -> int:
    """How many adjacent output columns the Pallas path folds into the
    lanes of a conv on the conv2d kernel: ``128 // C`` for a unit-stride
    conv of more than one tap whose C is below 128 and divides it (2 for
    VGG-16's conv1_2 and ResNet-50's conv2 3x3s, 4 for the pruned conv2
    3x3s' 32 channels), else 1.  Folded, each dot contracts and writes 128
    lanes, where C = K = 64 would fill a quarter of the MXU.  From the
    shapes alone; the one place that decides it."""
    fh, fw, _, _ = w_shape
    cin = x_shape[3]
    if (stride != 1 or fh * fw == 1 or runs_as_gemm(w_shape, stride)
            or cin >= LANES or LANES % cin):
        return 1
    return LANES // cin


def conv2d_kernel_shapes(x_shape, w_shape, stride: int, padding: int):
    """``(x_shape, w_shape, padding)`` of the conv the conv2d kernel runs
    (``kernels.ops._col_fold``): with :func:`col_fold` ``f > 1``, the input
    padded once and read as ``f*C`` lanes of ``OWf + TWf - 1`` column
    groups, ``OWf = ceil(OW / f)`` rounded up to a multiple of 8 (so the
    kernel's ``(th, OWf, 128)`` windows flatten without a relayout), the
    weights ``(FH, TWf, f*C, f*K)`` with ``TWf = ceil((f + FW - 1) / f)``
    column taps, and padding 0; else the conv's own."""
    f = col_fold(x_shape, w_shape, stride)
    if f == 1:
        return tuple(x_shape), tuple(w_shape), padding
    b, h, wd, cin = x_shape
    fh, fw, _, k = w_shape
    ow = wd + 2 * padding - fw + 1
    twf = -(-(f + fw - 1) // f)
    owf = _ceil_to(-(-ow // f), 8)
    return ((b, h + 2 * padding, owf + twf - 1, f * cin),
            (fh, twf, f * cin, f * k), 0)


def conv2d_tuning_key(x_shape, w_shape, stride: int, padding: int, dtype,
                      epilogue: str = "none") -> str:
    """The key under which the Pallas path looks up a conv's tiles: its
    im2col GEMM's (:func:`runs_as_gemm`), else the conv the conv2d kernel
    runs (:func:`conv2d_kernel_shapes`), so tiles tuned for an unfolded
    shape never reach a folded call."""
    if runs_as_gemm(w_shape, stride):
        return gemm_key(*conv2d_gemm_shape(x_shape, w_shape, stride, padding),
                        dtype, epilogue)
    xs, ws, pad = conv2d_kernel_shapes(x_shape, w_shape, stride, padding)
    return conv2d_key(xs, ws, stride, pad, dtype, epilogue)


def conv2d_gemm_shape(x_shape, w_shape, stride: int,
                      padding: int) -> tuple[int, int, int]:
    """(M, C, K) of the im2col GEMM a conv runs as on the Pallas path
    (:func:`runs_as_gemm`): one row per output pixel, one column per (tap,
    input channel) of the filter padded with zero taps to
    ``stride * ceil(F / stride)`` a side (``kernels.ops._im2col``): 192
    columns for the 7x7/2 stem, not 147; 27 for VGG-16's conv1_1."""
    b, h, w, cin = x_shape
    fh, fw, _, k = w_shape
    oh = (h - fh + 2 * padding) // stride + 1
    ow = (w - fw + 2 * padding) // stride + 1
    return (b * oh * ow,
            _ceil_to(fh, stride) * _ceil_to(fw, stride) * cin, k)


def _ep_none(key: str) -> str:
    """The epilogue-agnostic fallback key (tiling barely depends on the tag)."""
    return key[:key.rindex("|ep:")] + "|ep:none"


# ---------------------------------------------------------------------------
# Kernel-signature hash (invalidation)
# ---------------------------------------------------------------------------
_KERNELS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "kernels")
_HASHED_SOURCES = ("conv2d.py", "matmul.py")


def kernel_signature_hash() -> str:
    """Hash of the tunable-kernel sources; tables carry it, loaders check it."""
    h = hashlib.sha256()
    for name in _HASHED_SOURCES:
        with open(os.path.join(_KERNELS_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def tables_dir() -> str:
    """Committed tuned tables (env-overridable for tests)."""
    return os.environ.get("REPRO_TUNED_TABLES_DIR",
                          os.path.join(_KERNELS_DIR, "tuned"))


def cache_dir() -> str:
    """User tuning cache (env-overridable)."""
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro-autotune"))


# ---------------------------------------------------------------------------
# Cache state
# ---------------------------------------------------------------------------
class _State:
    def __init__(self) -> None:
        self.entries: dict[str, Entry] = {}
        self.stale_tables: list[dict] = []   # committed tables w/ bad hash


_state: _State | None = None
_enabled = os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0", "off")


def enabled() -> bool:
    """The hot-path gate: one module-attribute read, nothing else."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop the in-memory cache; the next lookup reloads from disk."""
    global _state
    _state = None


def _backend() -> str:
    import jax
    return jax.default_backend()


def _load_table(path: str, source: str, state: _State,
                cur_hash: str, backend: str) -> None:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return
    if doc.get("backend") != backend:
        return
    if doc.get("kernel_hash") != cur_hash:
        if source == "table":
            state.stale_tables.append(
                {"path": path, "table_hash": doc.get("kernel_hash"),
                 "current_hash": cur_hash})
        return
    for key, e in doc.get("entries", {}).items():
        # user cache outranks committed tables; runtime puts outrank both
        # (load order is table -> cache; put() happens after).
        state.entries[key] = Entry(
            config=TileConfig.from_dict(e["config"]), source=source,
            tuned_ms=e.get("tuned_ms", 0.0),
            default_ms=e.get("default_ms", 0.0))


def _ensure() -> _State:
    global _state
    if _state is None:
        st = _State()
        cur, backend = kernel_signature_hash(), _backend()
        tdir = tables_dir()
        if os.path.isdir(tdir):
            for name in sorted(os.listdir(tdir)):
                if name.endswith(".json"):
                    _load_table(os.path.join(tdir, name), "table", st,
                                cur, backend)
        cpath = os.path.join(cache_dir(), f"cache.{backend}.json")
        if os.path.exists(cpath):
            _load_table(cpath, "cache", st, cur, backend)
        _state = st
    return _state


def lookup(key: str) -> Entry | None:
    """O(1): exact key, then the epilogue-agnostic fallback."""
    entries = _ensure().entries
    hit = entries.get(key)
    if hit is None and not key.endswith("|ep:none"):
        hit = entries.get(_ep_none(key))
    return hit


def lookup_conv2d(x_shape, w_shape, stride, padding, dtype,
                  epilogue: str = "none") -> Entry | None:
    """A conv that runs as an im2col GEMM has GEMM tiles, a folded one its
    folded conv's (:func:`conv2d_tuning_key`)."""
    return lookup(conv2d_tuning_key(x_shape, w_shape, stride, padding, dtype,
                                    epilogue))


def lookup_gemm(m, c, k, dtype, epilogue: str = "none") -> Entry | None:
    return lookup(gemm_key(m, c, k, dtype, epilogue))


def put(key: str, config: TileConfig, *, source: str = "runtime",
        tuned_ms: float = 0.0, default_ms: float = 0.0) -> Entry:
    """Inject/overwrite an entry in the live cache (no disk write)."""
    e = Entry(config, source, tuned_ms, default_ms)
    _ensure().entries[key] = e
    return e


def stale_tables() -> list[dict]:
    """Committed tables whose kernel hash no longer matches the sources."""
    return list(_ensure().stale_tables)


# ---------------------------------------------------------------------------
# Persistence (the tuner writes through these)
# ---------------------------------------------------------------------------
def table_doc(entries: dict[str, Entry], *, impl: str = "pallas",
              net: str | None = None) -> dict:
    return {
        "version": 1,
        "backend": _backend(),
        "impl": impl,
        "net": net,
        "kernel_hash": kernel_signature_hash(),
        "entries": {
            key: {"config": e.config.to_dict(), "tuned_ms": e.tuned_ms,
                  "default_ms": e.default_ms}
            for key, e in sorted(entries.items())},
    }


def write_table(path: str, entries: dict[str, Entry], *,
                impl: str = "pallas", net: str | None = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(table_doc(entries, impl=impl, net=net), f, indent=2)
        f.write("\n")


def save_user_cache(entries: dict[str, Entry], *,
                    impl: str = "pallas") -> str:
    """Merge ``entries`` into the user cache file; returns its path."""
    path = os.path.join(cache_dir(), f"cache.{_backend()}.json")
    merged: dict[str, Entry] = {}
    if os.path.exists(path):
        st = _State()
        _load_table(path, "cache", st, kernel_signature_hash(), _backend())
        merged.update(st.entries)
    merged.update(entries)
    write_table(path, merged, impl=impl)
    reset()
    return path


# ---------------------------------------------------------------------------
# Cost-model-seeded candidate generation
# ---------------------------------------------------------------------------
_POW2 = (32, 64, 128, 256, 512)
# generous VMEM budget for ranking (interpret mode enforces nothing; on real
# TPUs ~16 MiB/core — candidates past this are deprioritized, not dropped)
VMEM_BUDGET = 16 * 2**20


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _clamp(t: int, dim: int) -> int:
    return max(1, min(t, dim))


def _lane_tiles(dim: int, sizes=_POW2) -> set[int]:
    """Tile sizes for a channel (lane) axis of length ``dim``: the whole axis
    or a multiple of 128 lanes, the only channel blocks Mosaic accepts."""
    return {_clamp(t, dim) for t in sizes if t >= dim or t % 128 == 0}


def conv2d_candidates(x_shape, w_shape, *, stride: int = 1, padding: int = 0,
                      max_candidates: int = 6) -> list[TileConfig]:
    """Tile candidates for the serial-accumulation conv kernel.

    Seeded by the cost model: candidates are ranked by padded-FLOPs waste
    (channel and last-row-block pads), then grid-step count, row blocks
    included, each from the tiles the kernel would run
    (``kernels.conv2d.step_tiles``).  Every candidate fits VMEM, since the
    kernel picks its row blocks to fit.
    The kernel defaults are always included.  A conv that runs as an im2col
    GEMM (:func:`runs_as_gemm`) takes :func:`gemm_candidates` of
    :func:`conv2d_gemm_shape` instead, and a folded one (:func:`col_fold`)
    the candidates of the conv the kernel runs (:func:`conv2d_kernel_shapes`).
    """
    from repro.kernels.conv2d import step_tiles
    x_shape, w_shape, padding = conv2d_kernel_shapes(x_shape, w_shape, stride,
                                                     padding)
    cin, k = x_shape[3], w_shape[3]
    oh = (x_shape[1] - w_shape[0] + 2 * padding) // stride + 1

    cands = {(_clamp(DEFAULT_CONV2D.bk, k), _clamp(DEFAULT_CONV2D.bc, cin))}
    for bk in _lane_tiles(k):
        for bc in _lane_tiles(cin):
            cands.add((bk, bc))

    def score(cand):
        th, bk, bc = step_tiles(x_shape, w_shape, padding=padding,
                                bk=cand[0], bc=cand[1])
        waste = (_ceil_to(k, bk) * _ceil_to(cin, bc) * _ceil_to(oh, th)
                 / (k * cin * oh))
        steps = -(-oh // th) * -(-k // bk) * -(-cin // bc)
        return (waste, steps, -cand[0] * cand[1])

    ranked = sorted(cands, key=score)[:max_candidates]
    return [TileConfig(bk=bk, bc=bc) for bk, bc in ranked]


def gemm_candidates(m: int, c: int, k: int, *,
                    max_candidates: int = 8) -> list[TileConfig]:
    """Candidates for the dual-stationarity GEMM — tiles AND the dataflow.

    Both stationarities are always represented (the empirical twin of the
    paper's §III.B/§III.C operand swap): weight-stationary keeps the whole
    ``(M, C)`` activation resident and streams each weight block once, in
    ``kernels.matmul.ws_blocks``' C blocks where a whole-C block does not
    fit, so it is a candidate at *any* M, not just the analytic M < 128 rule.
    Channel tiles are lane-legal (:func:`_lane_tiles`): the act-stationary
    kernel slices its resident block at ``c * bc`` lanes.
    """
    analytic_ws = select_stationarity(m) == Stationarity.WEIGHT_STATIONARY
    half = max(2, max_candidates // 2)

    as_cands = {(_clamp(DEFAULT_GEMM.bm, m), _clamp(DEFAULT_GEMM.bk, k),
                 _clamp(DEFAULT_GEMM.bc, c))}
    for bm in _POW2[:4]:
        for bk in _lane_tiles(k, _POW2[:4]):
            for bc in _lane_tiles(c):
                as_cands.add((_clamp(bm, m), bk, bc))

    def as_score(cand):
        bm, bk, bc = cand
        waste = (_ceil_to(m, bm) * _ceil_to(k, bk) * _ceil_to(c, bc)
                 / (m * k * c))
        steps = -(-m // bm) * -(-k // bk) * -(-c // bc)
        vmem = 4 * (bm * _ceil_to(c, bc) + bc * bk + bm * bk)
        return (waste, steps, vmem > VMEM_BUDGET, -bm * bk)

    ws_cands = _lane_tiles(k)           # holds the clamped default bk

    def ws_score(bk):
        from repro.kernels.matmul import ws_blocks
        bk, bc = ws_blocks(c, k, bk)
        waste = _ceil_to(k, bk) * _ceil_to(c, bc) / (k * c)
        return (waste, -(-k // bk) * -(-c // bc), -bk)

    out = [TileConfig(bk=bk, stationarity="weight_stationary")
           for bk in sorted(ws_cands, key=ws_score)[:half]]
    out += [TileConfig(bm=bm, bk=bk, bc=bc,
                       stationarity="activation_stationary")
            for bm, bk, bc in sorted(as_cands, key=as_score)[:half]]
    # analytic pick first: the search degrades gracefully under tight budgets
    out.sort(key=lambda t: (t.stationarity == "weight_stationary")
             != analytic_ws)
    return out[:max_candidates]


# ---------------------------------------------------------------------------
# tile_util — padding waste, the TPU analogue of the paper's PUF
# ---------------------------------------------------------------------------
def tile_util_conv2d(x_shape, w_shape, tiles: TileConfig | None = None, *,
                     stride: int = 1, padding: int = 0,
                     has_res: bool = False) -> float:
    """Logical FLOPs / the MXU's FLOPs under the conv kernel's tiling: each
    channel block of C and K takes 128 lanes of the MXU (a block of 64
    fills half of each), a last row block that runs past the plane computes
    padded rows, and a folded conv (:func:`col_fold`) computes its zero taps
    and padded column groups.  For a conv that runs as an im2col GEMM, the
    padded FLOPs of that GEMM's tiling, whose zero taps count as padding."""
    if runs_as_gemm(w_shape, stride):
        m, c, k = conv2d_gemm_shape(x_shape, w_shape, stride, padding)
        taps = w_shape[0] * w_shape[1] * w_shape[2]
        return taps / c * tile_util_gemm(
            m, c, k, tiles, stationarity=select_stationarity(m).value)
    from repro.kernels.conv2d import step_tiles
    fh, fw, cin, k = w_shape
    oh = x_shape[1] - fh + 2 * padding + 1
    ow = x_shape[2] - fw + 2 * padding + 1
    xk, wk, pk = conv2d_kernel_shapes(x_shape, w_shape, stride, padding)
    th, bk, bc = step_tiles(
        xk, wk, padding=pk, has_res=has_res,
        bk=tiles.bk if tiles and tiles.bk else DEFAULT_CONV2D.bk,
        bc=tiles.bc if tiles and tiles.bc else DEFAULT_CONV2D.bc)
    owk = xk[2] - wk[1] + 2 * pk + 1
    mxu = (wk[0] * wk[1] * -(-wk[2] // bc) * _ceil_to(bc, LANES)
           * -(-wk[3] // bk) * _ceil_to(bk, LANES) * _ceil_to(oh, th) * owk)
    return fh * fw * cin * k * oh * ow / mxu


def tile_util_gemm(m: int, c: int, k: int,
                   tiles: TileConfig | None = None,
                   stationarity: str | None = None) -> float:
    """Logical FLOPs / padded FLOPs for the GEMM under either stationarity."""
    st = (tiles.stationarity if tiles and tiles.stationarity
          else stationarity)
    if st == "weight_stationary":
        # (M, C) resident; K and, in C blocks, C are padded
        from repro.kernels.matmul import ws_blocks
        bk, bc = ws_blocks(c, k, tiles.bk if tiles and tiles.bk else None)
        return (k * c) / (_ceil_to(k, bk) * _ceil_to(c, bc))
    bk = _clamp((tiles.bk if tiles and tiles.bk else DEFAULT_GEMM.bk), k)
    bm = _clamp((tiles.bm if tiles and tiles.bm else DEFAULT_GEMM.bm), m)
    bc = _clamp((tiles.bc if tiles and tiles.bc else DEFAULT_GEMM.bc), c)
    return (m * c * k) / (_ceil_to(m, bm) * _ceil_to(c, bc) * _ceil_to(k, bk))
