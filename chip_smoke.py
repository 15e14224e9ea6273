"""Smoke check of the main path on one TPU: fused ResNet-50 through the kernels.

    python3 chip_smoke.py [--seed N]

Runs the paper's model, ResNet-50 at full width (224x224x3 input, fp32, fused
epilogues, random weights from ``--seed``), as one ``jax.jit`` of
``models.cnn.resnet50_apply(..., impl="pallas")`` in three phases: dense at
batch 1, dense at batch 8, and pruned at batch 1 (``resnet50_prune`` keeping
half the channels, applied outside the jit).  For each phase it prints the
compile seconds, the number of Mosaic kernels in the compiled program (one
``tpu_custom_call`` per conv dispatch, so nothing was interpreted and nothing
fell back to the XLA reference), the relative error of the logits against
``impl="ref"`` at the highest matmul precision, and milliseconds per forward.
The times are smoke readings on whatever chip ran them, not benchmark numbers.

Any failed check exits non-zero.  Without a TPU it exits non-zero having run
nothing.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.models.cnn import (  # noqa: E402
    resnet50_apply,
    resnet50_init,
    resnet50_prune,
)
from repro.runtime.compile_cache import use_compilation_cache  # noqa: E402

# max|logits - ref| / max|ref|.  Measured on a TPU v5e at seed 0: 1.0e-7 to
# 2.1e-7 with the kernels' dots at full fp32 precision, 2.26e-3 to 3.80e-3
# with Mosaic's default, which rounds fp32 operands to bf16.  The bound sits
# ~5000x above the first and only 2.2x below the closest of the second.
REL_ERR_BOUND = 1e-3
TIMED_CALLS = 5
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def conv_dispatches(params) -> int:
    """Conv dispatches of one forward: the stem, three per bottleneck block,
    and one per projection shortcut (53 for ResNet-50)."""
    blocks = [v for k, v in params.items() if "_b" in k]
    return 1 + sum(3 + ("proj" in blk) for blk in blocks)


def make_phases(seed: int, *, width: float = 1.0):
    """(name, params, x) for dense batch 1, dense batch 8 and pruned batch 1."""
    key = jax.random.PRNGKey(seed)
    params = jax.jit(functools.partial(resnet50_init, width=width))(key)
    x8 = jax.random.normal(jax.random.fold_in(key, 1), (8, 224, 224, 3))
    pruned, _ = resnet50_prune(params, 0.5)
    return [("dense_b1", params, x8[:1]),
            ("dense_b8", params, x8),
            ("pruned_b1", pruned, x8[:1])]


def run_phase(name: str, params, x, *, timed_calls: int = TIMED_CALLS) -> dict:
    """Compile, run and time the pallas forward; compare with the reference."""
    fwd = jax.jit(functools.partial(resnet50_apply, impl="pallas"))
    t0 = time.perf_counter()
    compiled = fwd.lower(params, x).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(params, x))
    ms = []
    for _ in range(timed_calls):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(params, x))
        ms.append((time.perf_counter() - t0) * 1e3)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(resnet50_apply, impl="ref"))(params, x)
    return {
        "phase": name,
        "platform": jax.devices()[0].platform,
        "batch": x.shape[0],
        "compile_s": compile_s,
        "kernel_calls": compiled.as_text().count(KERNEL_CALL),
        "conv_dispatches": conv_dispatches(params),
        "shape": tuple(out.shape),
        "finite": bool(jnp.all(jnp.isfinite(out))),
        "rel_err": float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref))),
        "ms": ms,
    }


def phase_failures(r: dict) -> list[str]:
    """What is wrong with one phase's result (empty when it passed).  Off a
    TPU the kernels are interpreted, so no Mosaic kernel is expected."""
    bad = []
    kernels = r["conv_dispatches"] if r["platform"] == "tpu" else 0
    if r["shape"] != (r["batch"], 1000):
        bad.append(f"logits shape {r['shape']}")
    if not r["finite"]:
        bad.append("non-finite logits")
    if not r["rel_err"] <= REL_ERR_BOUND:
        bad.append(f"rel err {r['rel_err']:.3e} > {REL_ERR_BOUND:.0e}")
    if r["kernel_calls"] != kernels:
        bad.append(f"{r['kernel_calls']} tpu_custom_calls, {kernels} expected "
                   f"for {r['conv_dispatches']} conv dispatches")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if os.environ.get("REPRO_IMPL", "pallas") != "pallas":
        print(f"chip_smoke: REPRO_IMPL={os.environ['REPRO_IMPL']!r} would "
              "replace the pallas path; unset it", file=sys.stderr)
        return 1

    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    print(f"compilation cache: {use_compilation_cache()}")
    failed = False
    for name, params, x in make_phases(args.seed):
        r = run_phase(name, params, x)
        bad = phase_failures(r)
        failed |= bool(bad)
        print(f"{name}: batch {r['batch']}  compile {r['compile_s']:.1f} s  "
              f"tpu_custom_call {r['kernel_calls']}/{r['conv_dispatches']}  "
              f"rel err {r['rel_err']:.3e} (bound {REL_ERR_BOUND:.0e})  "
              f"smoke reading: median {statistics.median(r['ms']):.3f} ms/"
              f"forward over {len(r['ms'])} calls "
              f"[{', '.join(f'{t:.3f}' for t in r['ms'])}]"
              + (f"  FAILED: {'; '.join(bad)}" if bad else ""), flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
