"""Telemetry-layer tests: span nesting/summation, zero-overhead disabled mode,
JSON round-trip, metrics percentiles, and the analytic-cost contract between
``carla_conv`` spans and ``core.cost_model.layer_cost``."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import carla_conv, layer_cost
from repro.core.networks import resnet50_conv_layers
from repro.observability import (
    LatencyWindow,
    MetricsRegistry,
    reconcile,
    totals,
    trace,
)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# ------------------------------- spans ----------------------------------------
def test_spans_nest_and_sum():
    trace.enable()
    with trace.span("outer") as outer:
        with trace.span("inner", flops=100):
            time.sleep(0.002)
        with trace.span("inner", flops=50):
            pass
    assert len(trace.tracer.spans) == 1          # one root
    root = trace.tracer.spans[0]
    assert [c.name for c in root.children] == ["inner", "inner"]
    # attr sums aggregate over the subtree; durations nest consistently
    assert root.total("flops") == 150
    assert root.duration_s >= sum(c.duration_s for c in root.children) > 0
    assert root.self_time_s() >= 0


def test_disabled_mode_records_nothing():
    assert not trace.enabled()
    with trace.span("ghost") as sp:
        assert sp is None
    x = jnp.ones((1, 8, 8, 4))
    w = jnp.ones((3, 3, 4, 8))
    carla_conv(x, w, padding=1)
    assert trace.tracer.spans == []


def test_no_timed_span_inside_jit_the_scope_names_the_layer():
    """Traced inside a jit, carla_conv opens no span (a span there would fire
    once, at trace time, and time the compile); the layer's named scope is
    its record in the compiled program.  Eager, the span is there."""
    x = jnp.ones((1, 8, 8, 4))
    w = jnp.ones((3, 3, 4, 8))
    trace.enable()
    f = jax.jit(lambda x, w: carla_conv(x, w, padding=1, name="res_l1"))
    f(x, w).block_until_ready()
    assert trace.tracer.spans == []
    assert "/res_l1/" in f.lower(x, w).compile().as_text()
    carla_conv(x, w, padding=1, name="res_l1")
    assert [s.name for s in trace.tracer.spans] == ["carla_conv"]
    assert [c.name for c in trace.tracer.spans[0].children] == ["kernels.conv2d"]


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """An enabled span also enters jax.profiler.TraceAnnotation, so a
    profiler trace holds it on its host plane, beside the device's ops."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.capture() as tr:
            with trace.span("res.host_span"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in tr.spans] == ["res.host_span"]
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    names = {e.name for plane in ProfileData.from_file(files[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "res.host_span" in names


def test_json_roundtrip_exact():
    trace.enable()
    with trace.span("a", mode="3x3", n=7):
        with trace.span("b", nested=True):
            pass
    payload = trace.tracer.to_json()
    restored = trace.tracer.from_json(payload)
    assert [s.to_dict() for s in restored] == \
        [s.to_dict() for s in trace.tracer.spans]
    assert restored[0].children[0].attrs == {"nested": True}


def test_capture_restores_prior_state():
    assert not trace.enabled()
    with trace.capture() as tr:
        assert trace.enabled()
        with trace.span("x"):
            pass
    assert not trace.enabled()
    assert len(tr.spans) == 1


def test_sequential_captures_preserve_prior_roots():
    """Regression: capture() used to clear the tracer, destroying spans
    collected before the block; prior roots must survive, and each capture
    must see only its own spans."""
    trace.enable()
    with trace.span("before"):
        pass
    with trace.capture() as tr1:
        with trace.span("a"):
            pass
    with trace.capture() as tr2:
        with trace.span("b"):
            pass
    assert [s.name for s in trace.tracer.spans] == ["before"]
    assert [s.name for s in tr1.spans] == ["a"]
    assert [s.name for s in tr2.spans] == ["b"]
    assert trace.enabled()                       # enabled flag restored too


def test_nested_captures_keep_outer_spans():
    with trace.capture() as outer:
        with trace.span("o1"):
            pass
        with trace.capture() as inner:
            with trace.span("i1"):
                pass
        with trace.span("o2"):
            pass
    assert [s.name for s in inner.spans] == ["i1"]
    assert [s.name for s in outer.spans] == ["o1", "o2"]
    assert not trace.enabled()
    assert trace.tracer.spans == []
    # Capture.find walks the captured forest like Tracer.find
    assert [s.name for s in outer.find("o2")] == ["o2"]


# ------------------- carla_conv spans vs the analytic model -------------------
def test_carla_span_analytic_cost_matches_layer_cost_exactly():
    """A ResNet-50 layer dispatched through carla_conv must record exactly
    the LayerCost numbers the analytic model computes for that layer."""
    layer = resnet50_conv_layers()[1]            # conv2_b0_1x1a, 56x56x64->64
    cost = layer_cost(layer)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, layer.IL, layer.IL, layer.IC))
    w = jax.random.normal(key, (layer.FL, layer.FL, layer.IC, layer.K))
    with trace.capture() as tr:
        carla_conv(x, w, stride=layer.S, padding=layer.Z, name=layer.name)
    (sp,) = tr.spans
    assert sp.name == "carla_conv"
    assert sp.attrs["layer"] == layer.name
    assert sp.attrs["dataflow"] == cost.dataflow.value
    assert sp.attrs["analytic_cycles"] == cost.cycles
    assert sp.attrs["analytic_dram_bytes"] == cost.dram_bytes
    assert sp.attrs["analytic_puf"] == cost.puf
    assert sp.attrs["analytic_time_ms"] == cost.time_s * 1e3
    assert sp.attrs["macs"] == layer.macs
    # the kernel it dispatched to is recorded as a child span
    assert len(sp.children) == 1
    assert sp.children[0].name.startswith("kernels.")
    assert sp.duration_s >= sp.children[0].duration_s


def test_reconcile_builds_rows_and_totals():
    x = jnp.ones((2, 14, 14, 16))
    with trace.capture() as tr:
        carla_conv(x, jnp.ones((3, 3, 16, 32)), padding=1, name="l33")
        carla_conv(x, jnp.ones((16, 32)), name="l11")
    rows = reconcile(tr.spans)
    assert [r.layer for r in rows] == ["l33", "l11"]
    assert all(r.batch == 2 for r in rows)
    assert all(r.measured_ms > 0 and r.achieved_gflops > 0 for r in rows)
    assert max(r.measured_util for r in rows) == pytest.approx(1.0)
    t = totals(rows)
    assert t["layers"] == 2
    assert t["analytic_ms"] == pytest.approx(sum(r.analytic_ms for r in rows))


# ------------------------------- metrics --------------------------------------
def test_latency_window_percentiles_exact():
    lw = LatencyWindow("step", maxlen=100)
    for v in range(1, 101):                      # 1..100 ms
        lw.observe(v / 1e3)
    assert lw.percentile(50) == pytest.approx(0.0505, abs=1e-3)
    assert lw.percentile(0) == pytest.approx(0.001)
    assert lw.percentile(100) == pytest.approx(0.100)
    # rolling: pushing 50 more evicts the oldest 50
    for v in range(101, 151):
        lw.observe(v / 1e3)
    assert lw.percentile(0) == pytest.approx(0.051)
    assert lw.count == 150                       # lifetime count keeps going


def test_latency_window_duplicates_across_eviction_boundary():
    """Duplicate values crossing the maxlen boundary: eviction must remove
    exactly one copy from the sorted mirror, keeping percentiles exact."""
    lw = LatencyWindow("dup", maxlen=4)
    for v in (0.005, 0.005, 0.005, 0.010):
        lw.observe(v)
    # evicts one 0.005; window is [0.005, 0.005, 0.010, 0.020]
    lw.observe(0.020)
    assert lw._sorted == [0.005, 0.005, 0.010, 0.020]
    assert lw.percentile(0) == pytest.approx(0.005)
    assert lw.percentile(100) == pytest.approx(0.020)
    # evict the remaining duplicates one at a time
    lw.observe(0.030)
    lw.observe(0.040)
    assert lw._sorted == [0.010, 0.020, 0.030, 0.040]
    assert len(lw._window) == len(lw._sorted) == 4


def test_latency_window_single_element_percentiles():
    lw = LatencyWindow("one", maxlen=8)
    lw.observe(0.042)
    for p in (0, 1, 50, 99, 100):
        assert lw.percentile(p) == pytest.approx(0.042)
    assert lw.summary()["p50_ms"] == pytest.approx(42.0)


def test_latency_window_lifetime_stats_include_evicted():
    lw = LatencyWindow("life", maxlen=2)
    for v in (0.001, 0.002, 0.003, 0.004):
        lw.observe(v)
    # window only holds the last 2, but lifetime count/mean keep everything
    assert len(lw._window) == 2
    assert lw.count == 4
    assert lw.total_s == pytest.approx(0.010)
    assert lw.mean_s == pytest.approx(0.0025)
    assert lw.percentile(0) == pytest.approx(0.003)   # window excludes evicted


def test_gauge_and_histogram_in_registry():
    from repro.observability import Histogram

    m = MetricsRegistry()
    g = m.gauge("queue_depth")
    g.inc(5)
    g.dec(2)
    assert m.gauge("queue_depth").value == 3
    h = m.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum[0.01] == 1 and cum[0.1] == 2 and cum[1.0] == 3
    assert cum[float("inf")] == h.count == 4
    assert h.sum == pytest.approx(5.555)
    snap = m.snapshot()
    assert snap["gauges"]["queue_depth"] == 3
    assert snap["histograms"]["lat"]["count"] == 4
    # boundary value lands in the bucket it equals (le semantics)
    h2 = Histogram("b", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert dict(h2.cumulative())[1.0] == 1


def test_metrics_registry_snapshot():
    m = MetricsRegistry()
    m.counter("tokens").inc(64)
    m.counter("tokens").inc(64)
    m.latency("step").observe(0.010)
    snap = m.snapshot()
    assert snap["counters"]["tokens"] == 128
    assert snap["latencies"]["step"]["count"] == 1
    assert snap["latencies"]["step"]["p50_ms"] == pytest.approx(10.0)


def test_scheduler_exposes_metrics():
    """The continuous batcher counts admissions/tokens and times steps."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.scheduler import ContinuousBatcher, Request

    cfg = get_config("smollm-135m", smoke=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_seq=32)
    prompt = jnp.arange(4, dtype=jnp.int32)
    b.submit(Request(0, prompt, max_new_tokens=3))
    b.submit(Request(1, prompt, max_new_tokens=3))
    done = b.run()
    assert len(done) == 2
    stats = b.stats()
    assert stats["counters"]["requests_admitted"] == 2
    assert stats["counters"]["requests_completed"] == 2
    assert stats["counters"]["tokens_generated"] >= 4
    assert stats["latencies"]["decode_step"]["count"] >= 2
    assert stats["tokens_per_s"] > 0
    assert 0 < stats["slot_occupancy"] <= 1
