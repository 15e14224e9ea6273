"""Benchmark driver: one table per paper figure + kernel bench + roofline.

Run:  PYTHONPATH=src python -m benchmarks.run  [--skip-kernels]
          [--smoke] [--bench-json BENCH_10.json] [--tuned] [--sparse]

``--bench-json`` measures the ResNet-50/VGG-16 layer sets — unfused and
through the fused-epilogue path — via traced ``carla_conv`` dispatches and
writes the per-layer measured ms / GFLOP/s / utilization / bytes record that
``benchmarks/check_regression.py`` gates against, plus the per-bottleneck-
block fused-vs-unfused HBM-bytes delta (``fused_delta``).
``--tuned`` enables the empirical tuning cache (committed tables +
``~/.cache/repro-autotune``) during the measurement and embeds the per-key
tuned-vs-default deltas (``tuning``) that the regression gate bands.
``--sparse`` additionally measures the structured-sparse twins of the layer
sets (paper Table I) through the real kernels and embeds the per-layer
dense-vs-sparse comparison (``sparse_delta``) the gate's sparse invariant
checks: every pruned layer must touch strictly fewer bytes and run no
slower than its dense twin.
``--smoke`` keeps everything in seconds: analytic tables + fidelity gate
only, and the bench record (if requested) uses the tiny smoke layer set.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.runtime.compile_cache import use_compilation_cache


def _print_table(title, headers, rows, max_rows=60):
    print(f"\n=== {title} ===")
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows
              else len(str(h)) for i, h in enumerate(headers)]
    line = "  ".join(str(h).ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-" * len(line))
    shown = rows if len(rows) <= max_rows else rows[:max_rows]
    for r in shown:
        print("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    if len(rows) > max_rows:
        print(f"... ({len(rows) - max_rows} more rows)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="analytic tables + fidelity gate only (seconds); "
                         "--bench-json uses the tiny smoke layer set")
    ap.add_argument("--bench-json", default=None,
                    help="measure the conv layer sets and write the "
                         "BENCH_*.json perf baseline here")
    ap.add_argument("--bench-reps", type=int, default=2,
                    help="traced reps per layer for --bench-json (best kept)")
    ap.add_argument("--tuned", action="store_true",
                    help="enable the tuning cache for --bench-json and embed "
                         "the tuned-vs-default deltas")
    ap.add_argument("--sparse", action="store_true",
                    help="also measure the structured-sparse layer-set twins "
                         "for --bench-json and embed the dense-vs-sparse "
                         "per-layer deltas (sparse_delta)")
    args = ap.parse_args()
    use_compilation_cache()

    from . import paper_figures

    ok = True
    for fn in paper_figures.ALL:
        title, headers, rows = fn()
        _print_table(title, headers, rows)

    # paper-fidelity gate: headline numbers must hold
    from repro.core import resnet50_cost, vgg16_cost
    checks = [
        ("ResNet-50 ms", resnet50_cost().time_ms, 92.7, 0.005),
        ("ResNet-50 MB", resnet50_cost().dram_mb, 124.0, 0.005),
        ("sparse ms", resnet50_cost(sparse=True).time_ms, 42.5, 0.005),
        ("sparse MB", resnet50_cost(sparse=True).dram_mb, 63.3, 0.011),
        ("VGG-16 ms", vgg16_cost().time_ms, 396.9, 0.011),
        ("VGG-16 MB", vgg16_cost().dram_mb, 258.2, 0.005),
    ]
    print("\n=== Paper-fidelity gate ===")
    for name, got, want, tol in checks:
        rel = abs(got - want) / want
        status = "PASS" if rel <= tol else "FAIL"
        ok &= status == "PASS"
        print(f"{status} {name:16s} got {got:8.2f}  paper {want:8.2f}  "
              f"delta {rel * 100:5.2f}% (tol {tol * 100:.1f}%)")

    if not args.skip_kernels and not args.smoke:
        from .kernel_bench import kernel_table
        _print_table(*kernel_table())

    if not args.smoke:
        from .roofline import roofline_table
        for mesh in ("single", "multi"):
            title, headers, rows = roofline_table(mesh)
            if rows:
                _print_table(title, headers, rows)

    if args.bench_json:
        from .telemetry_report import collect_bench
        # each net is measured unfused AND through the fused-epilogue path;
        # the ``<net>_fused`` runs also record the per-bottleneck-block
        # fused-vs-unfused bytes/latency delta (``fused_delta``).  The full
        # baseline also carries the smoke nets so ``check_regression --smoke``
        # (the tier-1 gate) can compare against the committed record.
        nets = (["smoke", "smoke_fused"] if args.smoke
                else ["smoke", "smoke_fused",
                      "resnet50", "resnet50_fused", "vgg16", "vgg16_fused"])
        if args.sparse:
            # sparse twins ride along; the delta pairs them with the dense
            # nets already in the list, so order doesn't matter
            nets += (["smoke_sparse"] if args.smoke
                     else ["smoke_sparse", "resnet50_sparse"])
        reps = 1 if args.smoke else args.bench_reps
        record = collect_bench(nets, reps=reps, smoke=args.smoke,
                               tuned=args.tuned)
        with open(args.bench_json, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        n_layers = sum(len(v["layers"]) for v in record["networks"].values())
        print(f"\nbench record: {n_layers} layers over "
              f"{'/'.join(record['networks'])} -> {args.bench_json}")
        for net, fd in record.get("fused_delta", {}).items():
            worst = min(fd["blocks"], key=lambda b: b["saved_mb"])
            print(f"fused epilogue [{net}]: {fd['total_saved_mb']:.1f} MB "
                  f"HBM round-trips saved over {len(fd['blocks'])} blocks, "
                  f"{fd['total_speedup']:.2f}x wall; min block saving "
                  f"{worst['saved_mb']:.2f} MB ({worst['block']})")
        for net, sd in record.get("sparse_delta", {}).items():
            print(f"sparse delta [{net}]: {sd['pruned_layers']} pruned "
                  f"layers touch {sd['total_saved_mb']:.1f} MB fewer bytes, "
                  f"{sd['total_dense_ms']:.1f} ms dense -> "
                  f"{sd['total_sparse_ms']:.1f} ms sparse "
                  f"({sd['total_speedup']:.2f}x wall)")
        for net, delta in record.get("tuning", {}).items():
            d, t = delta["total_default_ms"], delta["total_tuned_ms"]
            print(f"tuning [{net}]: defaults {d:.1f} ms -> tuned {t:.1f} ms "
                  f"({d / max(t, 1e-9):.2f}x) over {delta['keys_timed']} "
                  f"shape keys ({delta['keys_missing']} untuned)")

    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
