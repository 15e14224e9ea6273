"""Kernel micro-bench: Pallas kernels vs jnp oracle, correctness + time.

Every kernel is called through ``kernels.ops`` with ``impl="pallas"``, so
``ops`` decides from the platform whether Mosaic compiles it or the
interpreter runs it.  Off a TPU the wall times characterize XLA-CPU and the
interpreter only; they are liveness readings, not kernel speed.  The value
here is the sweep: every kernel x shape cell must stay within tolerance of
its oracle.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.modes import Stationarity
from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _row(name, pallas, oracle):
    err = float(jnp.max(jnp.abs(pallas() - oracle())))
    return [name, f"{_time(pallas):.0f}", f"{_time(oracle):.0f}", f"{err:.1e}"]


def kernel_table():
    key = jax.random.PRNGKey(0)
    rows = []

    cases = [
        ("conv2d 3x3 s1", (1, 28, 28, 32), (3, 3, 32, 64), dict(padding=1)),
        ("conv2d 7x7 s2 (im2col gemm)", (1, 56, 56, 3), (7, 7, 3, 32),
         dict(stride=2, padding=3)),
    ]
    for name, xs, ws, kw in cases:
        x, w = jax.random.normal(key, xs), jax.random.normal(key, ws)
        rows.append(_row(name,
                         lambda: ops.conv2d(x, w, impl="pallas", **kw),
                         lambda: ref.conv2d_ref(x, w, **kw)))

    for name, (m, c, k), st in [
            ("gemm act-stationary 1k^3", (1024, 1024, 1024),
             Stationarity.ACTIVATION_STATIONARY),
            ("gemm weight-stationary (decode)", (4, 2048, 1024),
             Stationarity.WEIGHT_STATIONARY)]:
        x = jax.random.normal(key, (m, c))
        w = jax.random.normal(key, (c, k))
        rows.append(_row(name,
                         lambda: ops.gemm(x, w, impl="pallas", stationarity=st),
                         lambda: ref.matmul_ref(x, w)))

    x3 = jax.random.normal(key, (2, 256, 512))
    w3 = jax.random.normal(key, (4, 512))
    rows.append(_row("conv1d causal d_conv=4",
                     lambda: ops.conv1d_causal(x3, w3, impl="pallas"),
                     lambda: ref.conv1d_causal_ref(x3, w3)))

    return (f"Kernel micro-bench (Pallas vs jnp oracle, "
            f"{jax.default_backend()})",
            ["kernel", "pallas us", "oracle us", "max err"], rows)
