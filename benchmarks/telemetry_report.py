"""Analytic-vs-measured reconciliation report (paper Table II, both sides).

Runs every conv layer of ResNet-50 (and VGG-16 with --net vgg16) through
``carla_conv`` with tracing enabled and prints, per layer:

  analytic (ASIC model, batch-1):  cycles, ms @ 200 MHz, DRAM MB, PUF %
  measured (this machine):         wall ms, array MB touched, GFLOP/s,
                                   util % vs the run's peak (or --peak-gflops)

Run:  PYTHONPATH=src python -m benchmarks.telemetry_report [--net resnet50]
          [--batch 1] [--reps 3] [--limit N] [--json out.json]
          [--smoke] [--fused] [--tuned] [--sparse]

``--sparse`` swaps in the structured-pruned twin of the layer set (paper
Table I: the first two convs of every bottleneck halve their filters, the
shortcut trunk stays dense) and tags every pruned dispatch with its dense
twin — the report's ``keep%`` column shows the kept MAC fraction per layer,
and the totals line reports the whole-net kept-MAC fraction.

``--tuned`` enables the empirical tuning cache (``core.autotune``) for the
run: dispatches whose shape key hits a committed/user tuned table run with
the measured tile sizes (and, for 1x1 layers, the measured stationarity),
and the report's ``tile%`` / ``tiles`` columns show the padding-waste PUF
analogue and which config actually ran — tuned-vs-default is visible per
layer by diffing a ``--tuned`` report against a default one.

``--fused`` dispatches every layer with a fused epilogue (folded-BN
scale/bias + ReLU, shortcut-add on bottleneck-closing 1x1s); the report's
``epilogue`` / ``savedMB`` columns show what was fused and the HBM
round-trip bytes the fusion eliminated per layer.

``--smoke`` swaps in the tiny ``smoke_conv_layers`` set (one layer per
dataflow, reps=1, overhead check skipped) so CI can keep this CLI alive in
seconds.

Also measures the tracing-disabled dispatch overhead (the acceptance gate for
the zero-overhead requirement): the same dispatch with tracing off must cost
the same as calling the jitted kernel directly.

``collect_bench`` is the shared measurement core behind the perf-regression
gate: ``benchmarks/run.py --bench-json`` writes its output as the committed
``BENCH_*.json`` baseline and ``benchmarks/check_regression.py`` compares a
fresh run against it.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (
    Epilogue,
    SparsityTag,
    autotune,
    carla_conv,
    epilogue_dram_delta_bytes,
)
from repro.core.networks import (
    resnet50_conv_layers,
    smoke_conv_layers,
    sparse_conv_layers,
    vgg16_conv_layers,
)
from repro.observability import format_table, reconcile, totals, trace
from repro.runtime.compile_cache import use_compilation_cache

NET_LAYERS = {
    "resnet50": resnet50_conv_layers,
    "vgg16": vgg16_conv_layers,
    "smoke": smoke_conv_layers,
}
# ``<net>_fused`` runs the same layer set with a per-layer fused epilogue
# (folded-BN scale/bias + ReLU; residual on the bottleneck-closing 1x1s).
FUSED_SUFFIX = "_fused"
# ``<net>_sparse`` runs the structured-pruned twin of the layer set, each
# pruned dispatch tagged with its dense twin (keep-fraction in the spans).
SPARSE_SUFFIX = "_sparse"


def _sparsity_tags(base: str) -> tuple[list, dict[str, SparsityTag]]:
    """Sparse twin layer set of ``base`` + per-layer dense-twin tags."""
    layers = sparse_conv_layers(base)
    dense = {l.name: l for l in NET_LAYERS[base]()}
    tags = {l.name: SparsityTag(dense_ic=dense[l.name].IC,
                                dense_k=dense[l.name].K)
            for l in layers
            if (dense[l.name].IC, dense[l.name].K) != (l.IC, l.K)}
    return layers, tags


def _layer_operands(layer, batch: int, key):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (batch, layer.IL, layer.IL, layer.IC),
                          jnp.float32)
    w = jax.random.normal(kw, (layer.FL, layer.FL, layer.IC, layer.K),
                          jnp.float32) * (layer.FL * layer.FL * layer.IC) ** -0.5
    return x, w


def _wants_residual(layer) -> bool:
    """Layers that close a bottleneck block get the shortcut add fused in."""
    return layer.name.endswith("_1x1b") or layer.name.endswith("_ws")


def _layer_epilogue(layer, batch: int, key) -> Epilogue:
    ks, kb, kr = jax.random.split(key, 3)
    scale = 1.0 + 0.1 * jax.random.normal(ks, (layer.K,), jnp.float32)
    bias = 0.1 * jax.random.normal(kb, (layer.K,), jnp.float32)
    residual = None
    if _wants_residual(layer):
        residual = jax.random.normal(
            kr, (batch, layer.OL, layer.OL, layer.K), jnp.float32)
    return Epilogue(scale=scale, bias=bias, relu=True, residual=residual)


def run_network(layers, batch: int, reps: int, impl: str = "auto",
                fused: bool = False, sparsity=None):
    """Warm every layer (compile), then record ``reps`` traced dispatches and
    keep each layer's best (min-wall) span — the compile-free steady state.

    ``sparsity``: optional ``{layer name: SparsityTag}`` for pruned layer
    sets — tagged dispatches record keep-fraction / dense-twin MACs."""
    key = jax.random.PRNGKey(0)
    best: dict[str, object] = {}
    for i, layer in enumerate(layers):
        x, w = _layer_operands(layer, batch, jax.random.fold_in(key, i))
        kw = dict(stride=layer.S, padding=layer.Z, impl=impl, name=layer.name)
        if sparsity and layer.name in sparsity:
            kw["sparsity"] = sparsity[layer.name]
        if fused:
            kw["epilogue"] = _layer_epilogue(layer, batch,
                                             jax.random.fold_in(key, 1000 + i))
        jax.block_until_ready(carla_conv(x, w, **kw))        # warm/compile
        for _ in range(reps):
            with trace.capture() as tr:
                carla_conv(x, w, **kw)
            (sp,) = tr.spans
            prev = best.get(layer.name)
            if prev is None or sp.duration_s < prev.duration_s:
                best[layer.name] = sp
    return [best[layer.name] for layer in layers]


# ----------------------- fused-vs-unfused block delta -------------------------
def _bottleneck_blocks(layers):
    """Group ResNet bottleneck triplets (1x1a, 3x3, 1x1b); anything else is
    its own single-layer 'block'."""
    blocks, i = [], 0
    while i < len(layers):
        l = layers[i]
        if (l.name.endswith("_1x1a") and i + 2 < len(layers)
                and layers[i + 1].name.endswith("_3x3")
                and layers[i + 2].name.endswith("_1x1b")):
            blocks.append((l.name[:-len("_1x1a")], layers[i:i + 3]))
            i += 3
        else:
            blocks.append((l.name, [l]))
            i += 1
    return blocks


def _run_block(layers, x0, weights, epilogues, fused: bool):
    """One forward through a block; returns (output, traced carla spans)."""
    with trace.capture() as tr:
        x = x0
        for layer, w, ep in zip(layers, weights, epilogues):
            kw = dict(stride=layer.S, padding=layer.Z, name=layer.name)
            if fused:
                x = carla_conv(x, w, epilogue=ep, **kw)
            else:
                x = carla_conv(x, w, **kw)
                x = x * ep.scale + ep.bias
                if ep.residual is not None:
                    x = x + ep.residual
                if ep.relu:
                    x = jnp.maximum(x, 0.0)
        jax.block_until_ready(x)
    return x, tr.spans


def collect_fused_delta(net: str, batch: int = 1, reps: int = 2,
                        smoke: bool = False) -> dict:
    """Measure each bottleneck block fused vs. unfused.

    Bytes are the spans' measured array footprints; the unfused side adds the
    HBM round-trips of its separate element-wise passes (one read + one write
    of the output fmap per op, plus the scale/bias/residual operand reads).
    The fused side must come out strictly lower on every block — that is the
    whole point of the epilogue.
    """
    layers = NET_LAYERS[net]()
    key = jax.random.PRNGKey(7)
    blocks_out = []
    for bi, (bname, blayers) in enumerate(_bottleneck_blocks(layers)):
        bkey = jax.random.fold_in(key, bi)
        first = blayers[0]
        x0 = jax.random.normal(jax.random.fold_in(bkey, 0),
                               (batch, first.IL, first.IL, first.IC),
                               jnp.float32)
        weights, epilogues = [], []
        for li, layer in enumerate(blayers):
            _, w = _layer_operands(layer, batch, jax.random.fold_in(bkey, li))
            weights.append(w)
            # residual on the block-closing layer (bottleneck shortcut add)
            ep = _layer_epilogue(layer, batch, jax.random.fold_in(bkey, 100 + li))
            if li != len(blayers) - 1 and ep.residual is not None:
                ep = Epilogue(scale=ep.scale, bias=ep.bias, relu=True)
            if li == len(blayers) - 1 and ep.residual is None and len(blayers) > 1:
                res = jax.random.normal(
                    jax.random.fold_in(bkey, 99),
                    (batch, layer.OL, layer.OL, layer.K), jnp.float32)
                ep = Epilogue(scale=ep.scale, bias=ep.bias, relu=True,
                              residual=res)
            epilogues.append(ep)

        stats = {}
        for mode, fused in (("fused", True), ("unfused", False)):
            _run_block(blayers, x0, weights, epilogues, fused)     # warm
            best_s, spans = float("inf"), None
            for _ in range(reps):
                t0 = time.perf_counter()
                _, sp = _run_block(blayers, x0, weights, epilogues, fused)
                dt = time.perf_counter() - t0
                if dt < best_s:
                    best_s, spans = dt, sp
            byts = sum(s.attrs["bytes_touched"] for s in spans)
            if not fused:
                # the element-wise passes the fused flush absorbs: each one
                # reads and rewrites the full output fmap, plus its operands
                for layer, ep in zip(blayers, epilogues):
                    out_b = 4 * batch * layer.OL * layer.OL * layer.K  # fp32
                    byts += 2 * out_b * ep.n_fused_ops
                    byts += sum(a.size * a.dtype.itemsize for a in
                                (ep.scale, ep.bias, ep.residual)
                                if a is not None)
            stats[mode] = {"ms": best_s * 1e3, "bytes": byts}

        blocks_out.append({
            "block": bname,
            "layers": len(blayers),
            "fused_ms": stats["fused"]["ms"],
            "unfused_ms": stats["unfused"]["ms"],
            "speedup": stats["unfused"]["ms"] / max(stats["fused"]["ms"], 1e-9),
            "fused_bytes_mb": stats["fused"]["bytes"] / 1e6,
            "unfused_bytes_mb": stats["unfused"]["bytes"] / 1e6,
            "saved_mb": (stats["unfused"]["bytes"]
                         - stats["fused"]["bytes"]) / 1e6,
            "analytic_saved_mb": sum(
                epilogue_dram_delta_bytes(
                    layer, scale_bias=True, relu=ep.relu,
                    residual=ep.residual is not None)
                for layer, ep in zip(blayers, epilogues)) / 1e6,
        })
    return {
        "blocks": blocks_out,
        "total_saved_mb": sum(b["saved_mb"] for b in blocks_out),
        "total_speedup": (sum(b["unfused_ms"] for b in blocks_out)
                          / max(sum(b["fused_ms"] for b in blocks_out), 1e-9)),
    }


def collect_sparse_delta(networks: dict) -> dict:
    """Pair each ``<base>_sparse`` record with its dense ``<base>`` twin.

    Layers pair by name (the sparse layer tables reuse the dense names), so
    per layer the delta carries measured ms/bytes on both sides plus the
    keep-fraction the spans recorded.  ``check_regression.py`` enforces the
    invariant on the ``pruned`` entries: strictly fewer bytes, and no slower
    than the dense twin beyond the noise band.
    """
    out: dict = {}
    for net, sn in networks.items():
        if not net.endswith(SPARSE_SUFFIX):
            continue
        base = net[:-len(SPARSE_SUFFIX)]
        dn = networks.get(base)
        if dn is None:
            continue
        dense = {l["layer"]: l for l in dn["layers"]}
        layers = []
        for sl in sn["layers"]:
            dl = dense.get(sl["layer"])
            if dl is None:
                continue
            layers.append({
                "layer": sl["layer"],
                "pruned": bool(sl.get("pruned", False)),
                "keep_fraction": sl.get("keep_fraction", 1.0),
                "dense_ms": dl["measured_ms"],
                "sparse_ms": sl["measured_ms"],
                "dense_bytes_mb": dl["bytes_mb"],
                "sparse_bytes_mb": sl["bytes_mb"],
                "saved_mb": dl["bytes_mb"] - sl["bytes_mb"],
                "speedup": dl["measured_ms"] / max(sl["measured_ms"], 1e-9),
            })
        pruned = [l for l in layers if l["pruned"]]
        out[base] = {
            "layers": layers,
            "pruned_layers": len(pruned),
            "total_dense_ms": sum(l["dense_ms"] for l in layers),
            "total_sparse_ms": sum(l["sparse_ms"] for l in layers),
            "total_saved_mb": sum(l["saved_mb"] for l in layers),
            "total_speedup": (sum(l["dense_ms"] for l in layers)
                              / max(sum(l["sparse_ms"] for l in layers),
                                    1e-9)),
        }
    return out


def collect_bench(nets: list[str], batch: int = 1, reps: int = 2,
                  impl: str = "auto", smoke: bool = False,
                  tuned: bool = False) -> dict:
    """Measure the given layer sets and return the BENCH_*.json record.

    Per layer: measured wall ms (best of ``reps``), achieved GFLOP/s,
    utilization vs the run's peak, plus the analytic side (ASIC ms, PUF) so
    regressions in achieved-vs-analytic are visible, not just wall time.

    A net named ``<base>_fused`` measures ``<base>``'s layer set through the
    fused-epilogue path (and triggers the per-bottleneck-block fused-vs-
    unfused delta measurement, recorded under ``fused_delta``).  A net named
    ``<base>_sparse`` measures the structured-pruned twin of ``<base>``'s
    layer set, every pruned dispatch tagged with its dense twin; when the
    dense ``<base>`` is measured in the same record, the per-layer dense-vs-
    sparse comparison lands under ``sparse_delta``.

    ``tuned=True`` enables the empirical tuning cache for the whole
    measurement (span attrs record ``tuned``/``tile_config``/``tile_util``)
    and additionally measures, per net, every tuned shape key through
    the pallas kernels with the tuned tiles vs the hardcoded defaults — the
    ``tuning`` section ``check_regression.py`` gates on.
    """
    record: dict = {
        "version": 4,
        "backend": jax.default_backend(),
        "impl": impl,
        "batch": batch,
        "reps": reps,
        "smoke": smoke,
        "tuned": tuned,
        "kernel_hash": autotune.kernel_signature_hash(),
        "networks": {},
        "fused_delta": {},
        "sparse_delta": {},
        "tuning": {},
    }
    prev_enabled = autotune.enabled()
    if tuned:
        autotune.enable()
    try:
        for net in nets:
            fused = net.endswith(FUSED_SUFFIX)
            base = net[:-len(FUSED_SUFFIX)] if fused else net
            sparse = base.endswith(SPARSE_SUFFIX)
            if sparse:
                base = base[:-len(SPARSE_SUFFIX)]
                layers, tags = _sparsity_tags(base)
            else:
                layers, tags = NET_LAYERS[base](), None
            spans = run_network(layers, batch, reps, impl, fused=fused,
                                sparsity=tags)
            rows = reconcile(spans)
            t = totals(rows)
            record["networks"][net] = {
                "total_measured_ms": t["measured_ms_per_image"],
                "total_analytic_ms": t["analytic_ms"],
                "speed_ratio": t["speed_ratio"],
                "total_fused_saved_mb": t["fused_saved_mb"],
                "mac_keep_fraction": t["mac_keep_fraction"],
                "layers": [{
                    "layer": r.layer,
                    "dataflow": r.dataflow,
                    "measured_ms": r.measured_ms,
                    "gflops": r.achieved_gflops,
                    "util_vs_peak": r.measured_util,
                    "analytic_ms": r.analytic_ms,
                    "analytic_puf": r.analytic_puf,
                    "epilogue": r.epilogue,
                    "bytes_mb": r.measured_bytes_mb,
                    "fused_saved_mb": r.fused_saved_mb,
                    "tile_util": r.tile_util,
                    "tuned": r.tuned,
                    "tile_config": r.tile_config,
                    "tuning_source": r.tuning_source,
                    "pruned": r.pruned,
                    "keep_fraction": r.keep_fraction,
                    "macs": r.macs,
                    "dense_twin_macs": r.dense_twin_macs,
                } for r in rows],
            }
            if fused:
                record["fused_delta"][base] = collect_fused_delta(
                    base, batch=batch, reps=reps, smoke=smoke)
            if tuned and net not in record["tuning"] and not fused:
                from .autotune import collect_tuning_delta
                record["tuning"][net] = collect_tuning_delta(
                    base, batch=batch, reps=reps,
                    layers=layers if sparse else None)
    finally:
        if tuned and not prev_enabled:
            autotune.disable()
    record["sparse_delta"] = collect_sparse_delta(record["networks"])
    return record


def measure_disabled_overhead(reps: int = 100,
                              trials: int = 7) -> tuple[float, float]:
    """Per-dispatch wall time: tracing disabled vs never-instrumented jit.

    Alternates instrumented/raw trials and keeps each side's minimum, so the
    comparison is robust to CPU frequency drift between the two measurements.
    """
    from repro.kernels import ops
    x = jnp.ones((1, 28, 28, 64))
    w = jnp.ones((3, 3, 64, 64))
    args = dict(stride=1, padding=1)

    def timed(fn):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(x, w, **args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6     # us

    trace.disable()
    jax.block_until_ready(ops.conv2d(x, w, **args))        # compile once
    wrapped = min(timed(ops.conv2d) for _ in range(trials))
    raw = min(timed(ops._conv2d_jit) for _ in range(trials))
    # interleave a second pass to wash out drift
    wrapped = min(wrapped, *(timed(ops.conv2d) for _ in range(trials)))
    raw = min(raw, *(timed(ops._conv2d_jit) for _ in range(trials)))
    return wrapped, raw


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", choices=["resnet50", "vgg16"], default="resnet50")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--limit", type=int, default=0,
                    help="only the first N layers (0 = all)")
    ap.add_argument("--impl", choices=["auto", "ref", "pallas"],
                    default="auto")
    ap.add_argument("--fused", action="store_true",
                    help="dispatch each layer with a fused epilogue "
                         "(folded-BN scale/bias + ReLU; residual on "
                         "bottleneck-closing 1x1s)")
    ap.add_argument("--sparse", action="store_true",
                    help="run the structured-pruned twin of the layer set "
                         "(paper Table I); pruned dispatches are tagged with "
                         "their dense twin (keep%% column)")
    ap.add_argument("--peak-gflops", type=float, default=0.0,
                    help="backend peak for util%% (0 = best layer in run)")
    ap.add_argument("--json", default=None,
                    help="also export the raw span trace to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny layer set, 1 rep, no overhead check (seconds)")
    ap.add_argument("--skip-overhead", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="enable the tuning cache for the run (tile%%/tiles "
                         "columns show what ran)")
    args = ap.parse_args()
    use_compilation_cache()

    if args.tuned:
        autotune.enable()

    if args.smoke:
        net, reps, skip_overhead = "smoke", 1, True
    else:
        net, reps, skip_overhead = args.net, args.reps, args.skip_overhead
    tags = None
    if args.sparse:
        layers, tags = _sparsity_tags(net)
        net = net + SPARSE_SUFFIX
    else:
        layers = NET_LAYERS[net]()
    if args.limit:
        layers = layers[:args.limit]

    print(f"=== {net}: analytic (ASIC @200 MHz, batch-1) vs measured "
          f"({jax.default_backend()}, batch={args.batch}, impl={args.impl}"
          f"{', fused epilogue' if args.fused else ''}) ===")
    spans = run_network(layers, args.batch, reps, args.impl, fused=args.fused,
                        sparsity=tags)
    rows = reconcile(spans, peak_gflops=args.peak_gflops or None)
    print(format_table(rows))

    t = totals(rows)
    print(f"\ntotals: {t['layers']} layers | analytic "
          f"{t['analytic_ms']:.1f} ms, {t['analytic_dram_mb']:.1f} DRAM MB | "
          f"measured {t['measured_ms_per_image']:.1f} ms/image, "
          f"{t['measured_bytes_mb']:.1f} MB arrays | "
          f"fused-epilogue HBM saved {t['fused_saved_mb']:.1f} MB | "
          f"wall/ASIC = {t['speed_ratio']:.2f}x")
    if t["pruned_layers"]:
        print(f"structured sparsity: {t['pruned_layers']} pruned layers, "
              f"{t['mac_keep_fraction'] * 100:.1f}% of dense-twin MACs kept")
    by_mode: dict[str, int] = {}
    for r in rows:
        by_mode[r.dataflow] = by_mode.get(r.dataflow, 0) + 1
    print("modes: " + ", ".join(f"{k}={v}" for k, v in sorted(by_mode.items())))

    if args.json:
        import json as _json
        with open(args.json, "w") as f:
            _json.dump([s.to_dict() for s in spans], f, indent=2)
        print(f"trace -> {args.json}")

    if not skip_overhead:
        wrapped, raw = measure_disabled_overhead()
        delta = wrapped - raw
        print(f"\ndisabled-tracing overhead: instrumented {wrapped:.1f} us vs "
              f"raw jit {raw:.1f} us per dispatch "
              f"(delta {delta:+.1f} us, {delta / raw * 100:+.1f}%)")


if __name__ == "__main__":
    main()
