"""Training launcher: supervised, checkpointed, restartable.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt

On this container it runs reduced configs on the (1,1) smoke mesh; on real
hardware the same entry point takes --mesh single|multi and the production
configs (the step functions, shardings, and checkpoint layout are identical).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import PrefetchIterator, SyntheticTokenDataset
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.observability import (
    MetricsExporter,
    MetricsRegistry,
    events,
    trace,
)
from repro.runtime import TrainSupervisor
from repro.runtime.compile_cache import use_compilation_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + (1,1) mesh (CPU)")
    ap.add_argument("--mesh", choices=["smoke", "single", "multi"],
                    default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--trace-out", default=None,
                    help="export the span trace to this JSON path")
    ap.add_argument("--metrics-port", type=int,
                    default=int(os.environ.get("REPRO_METRICS_PORT", "-1")),
                    help="serve Prometheus /metrics on this port "
                         "(0 = ephemeral, -1 = off; env REPRO_METRICS_PORT)")
    ap.add_argument("--event-log",
                    default=os.environ.get("REPRO_EVENT_LOG") or None,
                    help="append structured JSONL events to this path "
                         "(env REPRO_EVENT_LOG)")
    args = ap.parse_args()
    use_compilation_cache()
    if args.trace_out:
        trace.enable()
    if args.event_log:
        events.install(args.event_log)

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = (make_smoke_mesh() if args.mesh == "smoke" else
            make_production_mesh(multi_pod=args.mesh == "multi"))

    ds = SyntheticTokenDataset(cfg.vocab, args.seq_len, args.batch,
                               input_mode=cfg.input_mode,
                               d_model=cfg.d_model)

    with jax.set_mesh(mesh):
        mk = steps_mod.make_train_step(cfg, mesh, args.optimizer, args.lr)
        batch0 = ds.batch(0)
        batch_struct = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for k, v in batch0.items()}
        jitted = mk["jit"](batch_struct)

        sup = TrainSupervisor(args.ckpt_dir, ckpt_every=args.ckpt_every,
                              install_signal_handlers=True)
        state, start, data_idx = sup.restore_or_init(
            mk["make_init"](jax.random.PRNGKey(0)),
            jax.eval_shape(mk["make_init"](jax.random.PRNGKey(0))))
        if start:
            print(f"resumed from step {start} (data cursor {data_idx})")
        it = PrefetchIterator(ds, start_index=data_idx)

        def step_fn(state, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            return jitted(state, batch)

        t0 = time.time()
        telemetry = MetricsRegistry()
        exporter = None
        if args.metrics_port >= 0:
            exporter = MetricsExporter({"train": telemetry},
                                       port=args.metrics_port)
            print(f"metrics: http://127.0.0.1:{exporter.start()}/metrics")
        tokens_per_step = args.batch * args.seq_len

        def metrics_cb(step, metrics, dt):
            telemetry.counter("steps").inc()
            telemetry.counter("tokens").inc(tokens_per_step)
            telemetry.latency("train_step").observe(dt)
            telemetry.histogram("train_step_seconds").observe(dt)
            telemetry.gauge("last_loss").set(float(metrics["loss"]))
            if step % 10 == 0 or step < 3:
                print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                      f"{dt * 1e3:.0f} ms/step", flush=True)

        state, last, interrupted = sup.run(
            state, step_fn, it, start, args.steps, metrics_cb)
        it.close()
        status = "interrupted (checkpointed)" if interrupted else "done"
        print(f"{status} at step {last}; wall {time.time() - t0:.1f}s; "
              f"stragglers observed: {len(sup.straggler.events)}")
        lw = telemetry.latency("train_step")
        if lw.count:
            print(lw.format())
            print(f"throughput {telemetry.counter('tokens').value / lw.total_s:,.0f} tok/s")
        if args.trace_out:
            trace.tracer.export(args.trace_out)
            print(f"trace: {len(trace.tracer.spans)} spans -> {args.trace_out}")
        if exporter is not None:
            exporter.stop()
        if args.event_log:
            log = events.get()
            print(f"event log: {log.emitted if log else 0} events -> "
                  f"{args.event_log}")
            events.uninstall()


if __name__ == "__main__":
    main()
