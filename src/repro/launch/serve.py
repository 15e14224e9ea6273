"""Serving launcher: prefill a batch of prompts, then batched greedy decode.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --prompt-len 16 --gen 16 --batch 2

The decode loop donates the cache (in-place KV update), mirroring production
serving; the same step functions are what the decode_32k / long_500k dry-run
cells lower.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import lm
from repro.observability import MetricsExporter, MetricsRegistry, events
from repro.runtime.compile_cache import use_compilation_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", choices=["smoke", "single", "multi"],
                    default="smoke")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
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
    if args.event_log:
        events.install(args.event_log)

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = (make_smoke_mesh() if args.mesh == "smoke" else
            make_production_mesh(multi_pod=args.mesh == "multi"))
    max_seq = args.prompt_len + args.gen

    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = lm.init_params(cfg, key)
        if cfg.input_mode == "embeds":
            batch = {"embeds": jax.random.normal(
                key, (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16)}
        else:
            batch = {"tokens": jax.random.randint(
                key, (args.batch, args.prompt_len), 0, cfg.vocab)}

        telemetry = MetricsRegistry()
        exporter = None
        if args.metrics_port >= 0:
            exporter = MetricsExporter({"serve": telemetry},
                                       port=args.metrics_port)
            print(f"metrics: http://127.0.0.1:{exporter.start()}/metrics")
        t0 = time.time()
        logits, cache = lm.prefill(cfg, params, batch, max_seq=max_seq)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        telemetry.latency("prefill").observe(time.time() - t0)
        telemetry.counter("prompt_tokens").inc(args.batch * args.prompt_len)
        print(f"prefill {args.prompt_len} tokens x{args.batch}: "
              f"{(time.time() - t0) * 1e3:.0f} ms")

        mk = steps_mod.make_decode_step(cfg, mesh, max_seq=max_seq,
                                        batch_size=args.batch)
        out_tokens = [next_tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            ts = time.perf_counter()
            db = {"pos": jnp.full((args.batch,), args.prompt_len + i,
                                  jnp.int32)}
            if cfg.input_mode == "embeds":
                db["embeds"] = jax.random.normal(
                    jax.random.fold_in(key, i),
                    (args.batch, 1, cfg.d_model), jnp.bfloat16)
            else:
                db["token"] = next_tok.astype(jnp.int32)
            logits, cache = mk["fn"](params, cache, db)
            next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            jax.block_until_ready(next_tok)
            telemetry.latency("decode_token").observe(time.perf_counter() - ts)
            telemetry.counter("tokens_generated").inc(args.batch)
            out_tokens.append(next_tok)
        dt = (time.time() - t0) / max(1, args.gen - 1)
        toks = jnp.concatenate(out_tokens, axis=1)
        print(f"decoded {toks.shape[1]} tokens/seq @ {dt * 1e3:.0f} ms/token")
        lw = telemetry.latency("decode_token")
        if lw.count:
            print(lw.format())
        print("sample:", toks[0, :12].tolist())
        if exporter is not None:
            exporter.stop()
        if args.event_log:
            events.uninstall()


if __name__ == "__main__":
    main()
