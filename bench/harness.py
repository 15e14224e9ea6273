"""One cell of the benchmark, run once: set-up, the measured window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own that this module finds by the name
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the sizes; its ``model`` names the module
  beside it (``bench/configs/<model>.py``) that builds the weights from the
  seed, names the program's entry, and holds the plain reference and the
  count of work;
* ``bench/traffic/<traffic>.json``: the loop, the batch, the pool of distinct
  inputs and how many requests a traced window holds;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, which takes
  the reduced trace and the work and returns a number, or ``None`` when it
  finds nothing to read.

Nothing here touches a device at import.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import statistics
import tempfile
import time
from dataclasses import dataclass
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANNOTATIONS = ("request.dispatch", "request.wait")
WRONG = float(np.finfo(np.float64).max)  # the gap of logits that are not finite or of the wrong shape


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    model: object
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``, with its
    configuration, model module, traffic and metric readers."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, conf["file"]))
    bench = os.path.join(root, "bench")
    model = _load_module(os.path.join(bench, "configs", f"{config['model']}.py"),
                         f"bench_model_{config['model']}")
    traffic = _read_json(os.path.join(bench, "traffic", f"{w['traffic']}.json"))
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic {w['traffic']!r}: only a closed loop can be "
                         "driven; the program has no request queue")
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: _load_module(
        os.path.join(bench, "metrics", f"{m['name']}.py"),
        f"bench_metric_{m['name'].replace('.', '_')}") for m in per_layer}
    return Cell(workload, w["chips"], w["config"], config, model, traffic,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                per_layer, readers)


def seed_key(seed: int):
    """A PRNG key for any whole seed: the low 32 bits make the key and the
    bits above are folded in, so seeds past 2**32 stay distinct."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def peaks_for(kind: str, dtype: str, root: str = ROOT) -> SimpleNamespace:
    table = _read_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)})")
    return SimpleNamespace(flops=table[kind]["flops_per_s"][dtype],
                           hbm=table[kind]["hbm_bytes_per_s"])


@dataclass
class Window:
    start: float
    end: float
    latencies: list[float]
    outputs: list  # (pool index, logits on the device) per request


def closed_loop(forward, params, batches, *, seconds: float | None = None,
                requests: int | None = None) -> Window:
    """One request in flight at a time, cycling through ``batches``, until
    ``seconds`` have passed or ``requests`` have completed.  A request is one
    call of ``forward`` on a batch already on the device; it ends when the
    logits are ready.  The collector is off inside the window."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    lat, outs = [], []
    gc.disable()
    try:
        t0 = end = time.perf_counter()
        deadline = t0 + seconds if seconds is not None else float("inf")
        i = 0
        while requests is None or i < requests:
            ts = time.perf_counter()
            if ts >= deadline:
                break
            j = i % len(batches)
            with annotate(ANNOTATIONS[0]):
                y = forward(params, batches[j])
            with annotate(ANNOTATIONS[1]):
                y.block_until_ready()
            end = time.perf_counter()
            lat.append(end - ts)
            outs.append((j, y))
            i += 1
    finally:
        gc.enable()
    return Window(t0, end, lat, outs)


def check(cell: Cell, params, batches, outputs) -> dict:
    """Every request's logits against the plain float32 reference on the same
    images: the widest relative gap ``max|y - ref| / max|ref|`` over all
    requests, and the requests whose gap passes the configuration's limit or
    whose logits are not finite or of the wrong shape."""
    import jax
    cfg = cell.config
    limit = cfg["logits_rel_err_limit"]
    ref_fn = jax.jit(lambda p, x: cell.model.reference(cfg, p, x))
    refs = [np.asarray(ref_fn(params, x)) for x in batches]
    ys = jax.device_get([y for _, y in outputs])
    worst, failed = 0.0, 0
    for (j, _), y in zip(outputs, ys):
        r = refs[j]
        if y.shape != r.shape or not np.all(np.isfinite(y)):
            err = WRONG
        else:
            err = float(np.max(np.abs(y - r)) / np.max(np.abs(r)))
        failed += not err <= limit
        worst = max(worst, err)
    return {"logits_rel_err": (worst, limit), "failed_requests": (failed, 0)}


def _percentile(values, q: int) -> float:
    return statistics.quantiles(values, n=100)[q - 1] if len(values) > 1 else values[0]


def _trace_window(forward, params, batches, requests: int):
    """A closed-loop window of ``requests`` under the profiler; the parsed
    trace, read before its directory is removed."""
    import jax
    from bench import devtrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            win = closed_loop(forward, params, batches, requests=requests)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return win, devtrace.load_xspace(files[0])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, devices, width: float = 1.0,
             forward=None) -> dict:
    """Set up, measure for ``seconds``, check every answer; with ``trace``,
    also trace a short window and reduce it to the per-layer metrics.

    ``forward`` replaces the program's entry (tests break the timed path with
    it); ``width`` scales the channels (tests on the CPU)."""
    import jax
    from bench import devtrace
    marks = {"start": time.perf_counter()}
    cfg, traffic = cell.config, cell.traffic
    batch = traffic["batch"]
    key = seed_key(seed)
    params = cell.model.build(cfg, jax.random.fold_in(key, 0), width)
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    batches = cell.model.inputs(cfg, jax.random.fold_in(key, 1), traffic["pool"], batch)
    jax.block_until_ready(batches)
    marks["inputs"] = time.perf_counter()
    fwd = forward or cell.model.program_forward()
    for x in batches + batches[:1]:
        fwd(params, x).block_until_ready()
    marks["forward"] = time.perf_counter()
    setup_s = marks["forward"] - t_start

    win = closed_loop(fwd, params, batches, seconds=seconds)
    window_s = win.end - win.start
    images_per_s = len(win.latencies) * batch / window_s
    outputs = list(win.outputs)
    red = None
    if trace:
        tw, xspace = _trace_window(fwd, params, batches, traffic["trace_requests"])
        outputs += tw.outputs
        hlo = fwd.lower(params, batches[0]).compile().as_text()
        red = devtrace.reduce_trace(xspace, hlo, ANNOTATIONS)
        del xspace
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    checks = check(cell, params, batches, outputs)

    lat_ms = [t * 1e3 for t in win.latencies]
    summary = {"requests": len(lat_ms), "images": len(lat_ms) * batch,
               "window_s": window_s, "p50_ms": statistics.median(lat_ms),
               "p95_ms": _percentile(lat_ms, 95), "max_ms": max(lat_ms),
               "setup_s": setup_s,
               "setup_marks_s": {k: v - t_start for k, v in marks.items()}}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    result = {"summary": summary, "checks": checks, "device": device,
              "attempted": len(outputs)}
    if not trace:
        values = {"images_per_s": images_per_s,
                  "latency_p95_ms": summary["p95_ms"], "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        return result
    work = cell.model.work(cfg, params, batch)
    ctx = SimpleNamespace(
        trace=red, work=work, batch=batch, chips=cell.chips,
        requests=len(tw.latencies), images_per_s=images_per_s,
        flops_per_image=sum(layer["flops"] for layer in work) / batch,
        peaks=peaks_for(dev.device_kind, cfg["dtype"]))
    metrics = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    device.update(busy_s=red.busy_s, window_s=red.window_s)
    result["breakdown"] = devtrace.breakdown(red)
    result["trace_summary"] = {"requests": ctx.requests, "modules": red.modules,
                               "unknown_ops": red.unknown_ops,
                               "category_s": red.category_s}
    return result
