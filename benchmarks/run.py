"""The paper's tables from the analytic model, and its fidelity gate.

Run:  PYTHONPATH=src python -m benchmarks.run [--smoke]

Prints one table per paper figure (``benchmarks/paper_figures.py``) and
checks the headline numbers of the analytic ASIC model against the paper's
(exit 1 if one is off by more than its tolerance).  Without ``--smoke`` it
also prints the roofline tables of ``benchmarks/roofline.py``.  Nothing
here is timed: speed is measured only by ``bench/`` on the chip.
"""
from __future__ import annotations

import argparse
import sys


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
    ap.add_argument("--smoke", action="store_true",
                    help="analytic tables + fidelity gate only (seconds)")
    args = ap.parse_args()

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

    if not args.smoke:
        from .roofline import roofline_table
        for mesh in ("single", "multi"):
            title, headers, rows = roofline_table(mesh)
            if rows:
                _print_table(title, headers, rows)

    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
