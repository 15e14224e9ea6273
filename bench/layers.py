"""Per-layer device time of one cell, read from the program's own names.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s>
        [--trace-requests <n>] [--save DIR]

from the root of a checkout, on a machine that holds the cell's chips.  It
makes the cell's set-up as ``bench/run.py`` does, counts the compiles of
set-up with the program's counter (``runtime/compile_cache.py``), runs a
closed-loop window of ``--seconds`` with the profiler off and counts the
compiles inside it, then traces the cell's ``trace_requests`` (or
``--trace-requests``) under the profiler.  The trace is reduced twice: by
:mod:`bench.devtrace` (the benchmark's per-layer metrics) and by
:mod:`bench.scopes` (device time under each layer's named scope, the stem's
im2col alone, and the gaps inside each forward).  One JSON line ends standard
output.  ``--save DIR`` also writes the trace and the compiled program's
ENTRY computation there, gzipped, each Mosaic kernel's body cut down to its
kernel's name, as ``bench/testdata`` keeps them.  ``bench/run.py`` does not
call it: its numbers are not the benchmark's result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import base64  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def entry_text(hlo: str) -> str:
    """The module's first line and its ENTRY computation, each Mosaic
    kernel's serialized body replaced by ``kernel <its name>``."""
    start = hlo.index("\nENTRY ")
    end = hlo.index("\n}", start) + 2
    from bench import devtrace

    def cut(m):
        name = devtrace._kernel_name(m.group(0)) or "unknown"
        return '"body":"%s"' % base64.b64encode(f"kernel {name}".encode()).decode()
    body = re.sub(r'"body":"[A-Za-z0-9+/=]+"', cut, hlo[start:end])
    return hlo.splitlines()[0] + "\n" + body


def traced_window(forward, params, batches, requests: int):
    """``requests`` closed-loop requests under the profiler: the window and
    the path of the trace file, in a directory the caller removes."""
    import jax
    from bench import harness
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="bench-layers-")
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        win = harness.closed_loop(forward, params, batches, requests=requests)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return win, d, files[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-requests", type=int, default=None,
                    help="requests traced (default: the traffic's trace_requests)")
    ap.add_argument("--save", default=None,
                    help="directory for the gzipped trace and compiled text")
    args = ap.parse_args(argv)

    from bench import devtrace, harness, scopes
    from bench.run import find_chips
    from repro.runtime.compile_cache import compile_stats, use_compilation_cache
    import jax
    cell = harness.load_cell(args.workload)
    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = find_chips(cell.chips)
    if devices is None:
        return 2
    dev = devices[0]
    cfg, traffic = cell.config, cell.traffic
    batch = traffic["batch"]
    key = harness.seed_key(args.seed)
    params = cell.model.build(cfg, jax.random.fold_in(key, 0))
    batches = cell.model.inputs(cfg, jax.random.fold_in(key, 1), traffic["pool"], batch)
    fwd = cell.model.program_forward()
    for x in batches + batches[:1]:
        fwd(params, x).block_until_ready()
    setup_s = time.perf_counter() - T_START
    at_setup = compile_stats()

    win = harness.closed_loop(fwd, params, batches, seconds=args.seconds)
    at_window = compile_stats()
    untraced = len(win.latencies) * batch / (win.end - win.start)

    tw, tmp, path = traced_window(fwd, params, batches,
                                  args.trace_requests or traffic["trace_requests"])
    traced = len(tw.latencies) * batch / (tw.end - tw.start)
    try:
        hlo = fwd.lower(params, batches[0]).compile().as_text()
        if args.save:
            os.makedirs(args.save, exist_ok=True)
            stem = os.path.join(args.save, f"{cell.name}.{args.seed}")
            with open(path, "rb") as f, gzip.open(stem + ".xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)
            with gzip.open(stem + ".hlo.txt.gz", "wt") as g:
                g.write(entry_text(hlo))
        xspace = devtrace.load_xspace(path)
        red = devtrace.reduce_trace(xspace, hlo, harness.ANNOTATIONS)
        sc = scopes.reduce_scopes(xspace, hlo)
        host = sorted((e.start_ns, e.end_ns, e.name) for p in xspace.planes
                      if p.name.startswith("/host:") for line in p.lines
                      for e in line.events if e.name in harness.ANNOTATIONS)
        runs = [e.start_ns for p in xspace.planes
                if devtrace.DEVICE_PLANE.match(p.name) for line in p.lines
                if line.name == scopes.MODULES_LINE for e in line.events]
        offset_ms = devtrace._offset(host, runs, harness.ANNOTATIONS[0]) * 1e-6
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    work = cell.model.work(cfg, params, batch)
    peaks = harness.peaks_for(dev.device_kind, cfg["dtype"])
    ctx = SimpleNamespace(
        trace=red, work=work, batch=batch, chips=cell.chips,
        requests=len(tw.latencies), images_per_s=untraced,
        flops_per_image=sum(layer["flops"] for layer in work) / batch, peaks=peaks)
    old = {m["name"]: cell.readers[m["name"]].read(ctx) for m in cell.per_layer}
    out = {"workload": cell.name, "seed": args.seed, "device": dev.device_kind,
           "chips": len(devices), "setup_s": setup_s,
           "compiles": {"setup": at_setup["compiles"],
                        "setup_compile_s": at_setup["compile_s"],
                        "setup_cache_hits": at_setup["cache_hits"],
                        "window": at_window["compiles"] - at_setup["compiles"]},
           "images_per_s": {"untraced": untraced, "traced": traced,
                            "untraced_requests": len(win.latencies),
                            "traced_requests": len(tw.latencies)},
           "offset_ms": offset_ms, "benchmark_metrics": old,
           "category_ms": {k: 1e3 * v / ctx.requests for k, v in red.category_s.items()},
           "device_idle_share": 100.0 * (1.0 - red.busy_s / red.window_s)}
    if sc is None:
        out["scopes"] = None
    else:
        out["scopes"] = {
            "stem_im2col_ms": scopes.im2col_ms(sc),
            "forward_gap_ms": scopes.forward_gap_ms(sc),
            "conv1x1_ws_roofline": scopes.kernel_roofline(
                sc, work, peaks, scopes.WEIGHT_STATIONARY),
            "forward_ms": sc.per_forward_ms(sc.forward_s),
            "forwards": sc.forwards, "kernels": sc.kernel_count,
            "scoped_kernels": sc.scoped_kernels,
            "ws_layers": sorted(n for n, k in sc.kernels.items()
                                if k == scopes.WEIGHT_STATIONARY),
            "outside_ops": sc.outside_ops, "unknown_ops": sc.unknown_ops,
            "scope_ms": {k: sc.per_forward_ms(v) for k, v in sorted(sc.scope_s.items())}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
