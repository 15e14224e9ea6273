"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for.  Set-up makes the weights and a pool of input batches on the device from
``--seed``, compiles (or loads from the persistent compilation cache) the
program's forward and warms it on every batch.  The window then sends one
request at a time for ``--seconds``.  Afterwards every request's logits are
compared with the plain float32 reference.  With ``--trace 1`` a short window
of ``trace_requests`` more requests runs under the profiler, and the result
carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each compared number beside its limit.  The same
checks end standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def find_chips(need: int):
    """The TPU devices a cell may use, or ``None`` (with the reason on
    standard error) when JAX finds no TPU or too few of them."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        return None
    platform = devices[0].platform
    if platform != "tpu":
        print(f"bench: JAX found no TPU (platform {platform!r}); nothing was run",
              file=sys.stderr)
        return None
    if len(devices) < need:
        print(f"bench: the cell needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:need]


def result_line(result: dict) -> dict:
    """The driver's keys, in order, with ``checks`` last."""
    checks = result["checks"]
    failed = checks["failed_requests"][0]
    line = {"correct": all(v <= limit for v, limit in checks.values()),
            "attempted": result["attempted"], "failed": failed,
            "metrics": result["metrics"], "device": result["device"]}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = {name: {"value": v, "limit": limit}
                      for name, (v, limit) in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.runtime.compile_cache import use_compilation_cache
    import jax
    cell = harness.load_cell(args.workload)
    # Before the backend starts: set after jax.devices(), the directory went
    # unused on a TPU v5e, and every run compiled everything again.
    cache = use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = find_chips(cell.chips)
    if devices is None:
        return 2
    harness.peaks_for(devices[0].device_kind, cell.config["dtype"])

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, devices=devices)
    s = result["summary"]
    print(f"{cell.name}: seed {args.seed}, {devices[0].device_kind} "
          f"x{len(devices)}, compilation cache {cache}")
    print(f"window {s['window_s']:.6f} s: {s['requests']} requests, "
          f"{s['images']} images, p50 {s['p50_ms']:.6f} ms, "
          f"p95 {s['p95_ms']:.6f} ms, max {s['max_ms']:.6f} ms; setup {s['setup_s']:.6f} s, done at "
          + ", ".join(f"{k} {v:.3f} s" for k, v in s["setup_marks_s"].items()))
    if "trace_summary" in result:
        print("trace: " + json.dumps(result["trace_summary"]))
    line = result_line(result)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
