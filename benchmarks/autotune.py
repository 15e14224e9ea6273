"""Empirical per-layer autotuner: search tile sizes AND dataflow by measuring.

CARLA's controller picks a dataflow per layer analytically (§III); the Multi-
Mode Inference Engine line of work picks the per-layer operating point
*empirically*.  This CLI is the empirical side for our Pallas kernels: for
every unique (layer shape, dtype, epilogue, backend) key of a network it

  1. generates a cost-model-seeded candidate set (``core.autotune``):
     ``bk/bc`` channel tiles for the serial-accumulation conv kernel,
     ``bm/bk/bc`` tiles x both stationarities for the dual-residency GEMM
     (1x1 layers flatten to their GEMM shape, so ``conv1x1``/``gemm`` share
     entries, the convs that run as an im2col GEMM, strided or with few
     patch columns, key by that GEMM's shape, and those that fold columns
     into the lanes by the folded conv's);
  2. times each candidate through the jitted kernel wrappers
     (best-of-``reps`` wall time, compile excluded), *including the hardcoded
     defaults* — the PR 8 operating point;
  3. persists the winner keyed by shape into the user tuning cache
     (``~/.cache/repro-autotune/cache.<backend>.json``), or — with
     ``--commit`` — into a committed table under ``src/repro/kernels/tuned/``
     that ships with the repo and is invalidated by kernel-source hash.

Run:  PYTHONPATH=src python -m benchmarks.autotune --net resnet50 --commit
          [--reps 2] [--candidates 6] [--batch 1] [--out table.json]
          [--smoke] [--sparse]

``--sparse`` appends the structured-sparse twin of the layer set (pruned
channel counts are *new* shape keys), so sparse dispatches get their own
empirically tuned tiles instead of falling back to the hardcoded defaults.

``--smoke`` tunes the tiny smoke layer set with a minimal budget (seconds) —
the tier-1 liveness mode.  Tuning always measures the *pallas* kernels (tiles
are a Pallas concept; the ``ref`` path has no knobs), regardless of what
``impl`` the model later dispatches with.

``collect_tuning_delta`` re-measures tuned-vs-default fresh for every key a
loaded table covers; ``benchmarks/run.py --bench-json --tuned`` embeds its
output in the BENCH record and ``benchmarks/check_regression.py`` gates that
tuned never lost to the defaults beyond the noise band.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.autotune import Entry, TileConfig
from repro.core.networks import (
    resnet50_conv_layers,
    smoke_conv_layers,
    sparse_conv_layers,
    vgg16_conv_layers,
)
from repro.runtime.compile_cache import use_compilation_cache

NET_LAYERS = {
    "resnet50": resnet50_conv_layers,
    "vgg16": vgg16_conv_layers,
    "smoke": smoke_conv_layers,
}


def _gemm_rows(layer, batch: int) -> int:
    """M of the flattened 1x1 GEMM: the strided view's row count."""
    per_axis = -(-layer.IL // layer.S)
    return batch * per_axis * per_axis


def _conv_shapes(layer, batch: int):
    return ((batch, layer.IL, layer.IL, layer.IC),
            (layer.FL, layer.FL, layer.IC, layer.K))


def _gemm_shape(layer, batch: int):
    """(M, C, K) of the GEMM a layer runs as: a 1x1 flattens to its strided
    view's rows, a conv that ``autotune.runs_as_gemm`` routes to its im2col
    patches.  None for a layer that runs the conv2d kernel."""
    if layer.FL == 1:
        return _gemm_rows(layer, batch), layer.IC, layer.K
    x_shape, w_shape = _conv_shapes(layer, batch)
    if autotune.runs_as_gemm(w_shape, layer.S):
        return autotune.conv2d_gemm_shape(x_shape, w_shape, layer.S, layer.Z)
    return None


def _layer_key(layer, batch: int, dtype="float32") -> str:
    """The key the dispatch looks the layer up by: a 1x1's GEMM, else
    ``autotune.conv2d_tuning_key`` (an im2col GEMM's, or the folded conv's
    where ``autotune.col_fold`` folds columns into the lanes)."""
    if layer.FL == 1:
        return autotune.gemm_key(*_gemm_shape(layer, batch), dtype)
    return autotune.conv2d_tuning_key(*_conv_shapes(layer, batch), layer.S,
                                      layer.Z, dtype)


def _best_of(fn, reps: int) -> float:
    """Best-of-``reps`` wall ms; one untimed call first (compile/warm)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _timer_for(layer, batch: int, key, reps: int):
    """Returns ``time_ms(tiles)`` measuring the layer's pallas kernel."""
    from repro.kernels import ops
    if layer.FL == 1:
        m = _gemm_rows(layer, batch)
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (m, layer.IC), jnp.float32)
        w = jax.random.normal(kw, (layer.IC, layer.K), jnp.float32)

        def time_ms(tiles: TileConfig | None) -> float:
            return _best_of(lambda: ops._gemm_jit(x, w, impl="pallas",
                                                  tiles=tiles), reps)
        return time_ms
    kx, kw = jax.random.split(key)
    x_shape, w_shape = _conv_shapes(layer, batch)
    x = jax.random.normal(kx, x_shape, jnp.float32)
    w = jax.random.normal(kw, w_shape, jnp.float32)

    def time_ms(tiles: TileConfig | None) -> float:
        return _best_of(lambda: ops._conv2d_jit(x, w, stride=layer.S,
                                                padding=layer.Z,
                                                impl="pallas", tiles=tiles),
                        reps)
    return time_ms


def _candidates_for(layer, batch: int, max_candidates: int):
    gemm = _gemm_shape(layer, batch)
    if gemm is not None:
        return autotune.gemm_candidates(*gemm, max_candidates=max_candidates)
    return autotune.conv2d_candidates(*_conv_shapes(layer, batch),
                                      stride=layer.S, padding=layer.Z,
                                      max_candidates=max_candidates)


def tune_layers(layers, *, batch: int = 1, reps: int = 2,
                max_candidates: int = 6, log=None,
                verbose=False) -> dict[str, Entry]:
    """Search every unique shape key of ``layers``; return winning entries.

    The hardcoded-default timing is measured separately (``tiles=None``) and
    recorded in each entry, so downstream gates can always compare the tuned
    operating point against the PR 8 constants on the same machine.
    """
    entries: dict[str, Entry] = {}
    seed = jax.random.PRNGKey(0)
    for i, layer in enumerate(layers):
        key = _layer_key(layer, batch)
        if key in entries:
            continue
        timer = _timer_for(layer, batch, jax.random.fold_in(seed, i), reps)
        default_ms = timer(None)
        best_ms, best_cfg = float("inf"), None
        for cfg in _candidates_for(layer, batch, max_candidates):
            ms = timer(cfg)
            if ms < best_ms:
                best_ms, best_cfg = ms, cfg
            if log and verbose:
                log(f"  {key}  {cfg.short:<24s} {ms:8.2f} ms")
        entries[key] = Entry(config=best_cfg, source="cache",
                             tuned_ms=best_ms, default_ms=default_ms)
        if log:
            log(f"{layer.name:>22s}  default {default_ms:8.2f} ms -> "
                f"tuned {best_ms:8.2f} ms "
                f"({default_ms / max(best_ms, 1e-9):.2f}x)  "
                f"[{best_cfg.short}]")
    return entries


def collect_tuning_delta(net: str, *, batch: int = 1,
                         reps: int = 2, layers=None) -> dict:
    """Fresh tuned-vs-default measurement for every key a table covers.

    Uses whatever the tuning cache currently resolves (committed tables +
    user cache); keys with no entry are reported untimed so coverage gaps are
    visible rather than silently dropped.  ``layers`` overrides the layer
    set (e.g. the structured-sparse twin of ``net``).
    """
    if layers is None:
        layers = NET_LAYERS[net]()
    seed = jax.random.PRNGKey(3)
    seen: set[str] = set()
    out = []
    for i, layer in enumerate(layers):
        key = _layer_key(layer, batch)
        if key in seen:
            continue
        seen.add(key)
        entry = autotune.lookup(key)
        if entry is None:
            out.append({"layer": layer.name, "key": key, "tuned": False})
            continue
        timer = _timer_for(layer, batch, jax.random.fold_in(seed, i), reps)
        default_ms = timer(None)
        tuned_ms = timer(entry.config)
        out.append({
            "layer": layer.name, "key": key, "tuned": True,
            "tile_config": entry.config.short,
            "tuning_source": entry.source,
            "default_ms": default_ms, "tuned_ms": tuned_ms,
            "speedup": default_ms / max(tuned_ms, 1e-9),
        })
    timed = [e for e in out if e["tuned"]]
    return {
        "impl": "pallas",
        "layers": out,
        "keys_timed": len(timed),
        "keys_missing": len(out) - len(timed),
        "total_default_ms": sum(e["default_ms"] for e in timed),
        "total_tuned_ms": sum(e["tuned_ms"] for e in timed),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", choices=sorted(NET_LAYERS), default="resnet50")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--candidates", type=int, default=6,
                    help="max candidates timed per shape key")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny layer set, minimal budget (CI liveness)")
    ap.add_argument("--sparse", action="store_true",
                    help="also tune the structured-sparse (pruned-channel) "
                         "twin of the layer set, so sparse dispatches hit "
                         "tuned tiles instead of falling back to defaults")
    ap.add_argument("--commit", action="store_true",
                    help="write the committed table under "
                         "src/repro/kernels/tuned/ instead of the user cache")
    ap.add_argument("--out", default=None,
                    help="explicit output path (overrides --commit/cache)")
    ap.add_argument("--verbose", action="store_true",
                    help="print every candidate timing, not just winners")
    args = ap.parse_args()
    use_compilation_cache()

    net = "smoke" if args.smoke else args.net
    reps = 1 if args.smoke else args.reps
    cands = min(args.candidates, 3) if args.smoke else args.candidates
    layers = NET_LAYERS[net]()
    if args.sparse:
        layers = layers + sparse_conv_layers(net)

    print(f"=== autotune {net}: {len(layers)} layers, batch={args.batch}, "
          f"impl=pallas ({jax.default_backend()}), reps={reps}, "
          f"<= {cands} candidates/key ===")
    t0 = time.perf_counter()
    entries = tune_layers(layers, batch=args.batch, reps=reps,
                          max_candidates=cands, log=print,
                          verbose=args.verbose)
    dt = time.perf_counter() - t0

    total_def = sum(e.default_ms for e in entries.values())
    total_tun = sum(e.tuned_ms for e in entries.values())
    print(f"\n{len(entries)} unique shape keys tuned in {dt:.1f} s | "
          f"defaults {total_def:.1f} ms -> tuned {total_tun:.1f} ms "
          f"({total_def / max(total_tun, 1e-9):.2f}x over the key set)")

    if args.out:
        autotune.write_table(args.out, entries, net=net)
        print(f"tuned table -> {args.out}")
    elif args.commit:
        path = os.path.join(autotune.tables_dir(),
                            f"{net}.{jax.default_backend()}.json")
        autotune.write_table(path, entries, net=net)
        print(f"committed tuned table -> {path} "
              f"(kernel hash {autotune.kernel_signature_hash()})")
    else:
        path = autotune.save_user_cache(entries)
        print(f"user tuning cache -> {path}")


if __name__ == "__main__":
    main()
