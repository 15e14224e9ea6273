"""Export-layer tests: Prometheus exposition, the HTTP exporter, the JSONL
event log (and its instrumentation sites)."""
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from repro.observability import (
    MetricsExporter,
    MetricsRegistry,
    events,
    prom,
    trace,
)


@pytest.fixture(autouse=True)
def _clean_state():
    trace.disable()
    trace.clear()
    events.uninstall()
    yield
    trace.disable()
    trace.clear()
    events.uninstall()


# ----------------------- prometheus exposition --------------------------------
def _sample_registry():
    m = MetricsRegistry()
    m.counter("requests_admitted").inc(3)
    m.gauge("queue_depth").set(2)
    h = m.histogram("step_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 5.0):
        h.observe(v)
    m.latency("prefill").observe(0.02)
    return m


def test_prom_render_exposition_format():
    text = prom.render(_sample_registry(), namespace="repro")
    lines = text.splitlines()
    assert "repro_requests_admitted_total 3" in lines
    assert "# TYPE repro_requests_admitted_total counter" in lines
    assert "repro_queue_depth 2" in lines
    assert "# TYPE repro_queue_depth gauge" in lines
    assert "# TYPE repro_step_seconds histogram" in lines
    assert 'repro_step_seconds_bucket{le="0.01"} 1' in lines
    assert 'repro_step_seconds_bucket{le="0.1"} 2' in lines
    assert 'repro_step_seconds_bucket{le="1"} 2' in lines
    assert 'repro_step_seconds_bucket{le="+Inf"} 3' in lines
    assert "repro_step_seconds_count 3" in lines
    assert any(line.startswith("repro_step_seconds_sum") for line in lines)
    assert "# TYPE repro_prefill_seconds summary" in lines
    assert 'repro_prefill_seconds{quantile="0.5"} 0.02' in lines
    assert "repro_prefill_seconds_count 1" in lines
    # bucket counts must be cumulative (monotone non-decreasing)
    buckets = [int(line.rsplit(" ", 1)[1]) for line in lines
               if line.startswith("repro_step_seconds_bucket")]
    assert buckets == sorted(buckets)
    assert text.endswith("\n")


def test_prom_name_sanitization():
    m = MetricsRegistry()
    m.counter("tokens/sec-rate").inc()
    text = prom.render(m, namespace="repro")
    assert "repro_tokens_sec_rate_total 1" in text


def test_metrics_http_exporter_serves_scrape():
    reg = _sample_registry()
    ex = MetricsExporter({"serve": reg})
    port = ex.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "repro_serve_requests_admitted_total 3" in body
        # scrapes are live: mutate and re-scrape
        reg.counter("requests_admitted").inc()
        body2 = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "repro_serve_requests_admitted_total 4" in body2
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read()
        assert health == b"ok\n"
    finally:
        ex.stop()


# ----------------------------- event log --------------------------------------
def test_event_log_schema_and_threading(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events.install(path)
    assert events.enabled()
    events.emit("scheduler.admit", rid=1, slot=0, prompt_tokens=4)
    events.emit("train.step", step=0, dt_s=0.01, straggler=False)
    events.uninstall()
    assert not events.enabled()
    recs = list(events.read(path))
    assert [r["kind"] for r in recs] == ["scheduler.admit", "train.step"]
    assert all("ts" in r for r in recs)
    assert recs[0]["rid"] == 1 and recs[0]["slot"] == 0
    # disabled emit is a no-op, not an error
    events.emit("ghost.event", x=1)
    assert len(list(events.read(path))) == 2


def test_scheduler_emits_admit_complete_evict(tmp_path):
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.scheduler import ContinuousBatcher, Request

    path = str(tmp_path / "sched.jsonl")
    events.install(path)
    cfg = get_config("smollm-135m", smoke=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(cfg, params, batch_slots=1, max_seq=32)
    prompt = jnp.arange(4, dtype=jnp.int32)
    b.submit(Request(0, prompt, max_new_tokens=2))
    b.submit(Request(1, prompt, max_new_tokens=2))
    b.run()
    events.uninstall()
    kinds = [r["kind"] for r in events.read(path)]
    assert kinds.count("scheduler.admit") == 2
    assert kinds.count("scheduler.complete") == 2
    assert kinds.count("scheduler.evict") == 2
    # slot reuse is visible in the log: request 1 admitted after 0 evicted
    recs = list(events.read(path))
    evict0 = next(i for i, r in enumerate(recs)
                  if r["kind"] == "scheduler.evict" and r["rid"] == 0)
    admit1 = next(i for i, r in enumerate(recs)
                  if r["kind"] == "scheduler.admit" and r["rid"] == 1)
    assert evict0 < admit1


def test_supervisor_emits_step_and_checkpoint_events(tmp_path):
    from repro.data import PrefetchIterator, SyntheticTokenDataset
    from repro.runtime import TrainSupervisor

    path = str(tmp_path / "train.jsonl")
    events.install(path)
    ds = SyntheticTokenDataset(vocab=64, seq_len=8, global_batch=2)

    def step_fn(state, batch):
        return state, {}

    sup = TrainSupervisor(str(tmp_path / "ckpt"), ckpt_every=2)
    it = PrefetchIterator(ds, start_index=0)
    sup.run({"w": jnp.zeros((4,))}, step_fn, it, 0, 4)
    it.close()
    events.uninstall()
    recs = list(events.read(path))
    kinds = [r["kind"] for r in recs]
    assert kinds.count("train.step") == 4
    assert kinds.count("fault.checkpoint") == 2     # steps 2 and 4
    assert kinds[-1] == "data.closed"
    steps = [r["step"] for r in recs if r["kind"] == "train.step"]
    assert steps == [0, 1, 2, 3]


def test_elastic_remesh_emits_event(tmp_path):
    from repro.runtime import plan_remesh

    path = str(tmp_path / "elastic.jsonl")
    events.install(path)
    plan_remesh((16, 16), ("data", "model"), devices_available=208)
    events.uninstall()
    (rec,) = events.read(path)
    assert rec["kind"] == "elastic.remesh"
    assert rec["old_shape"] == [16, 16]
    assert rec["new_shape"] == [8, 16]
    assert rec["grad_accum_factor"] == 2
