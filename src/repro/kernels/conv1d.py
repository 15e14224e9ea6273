"""Depthwise causal 1-D convolution — CARLA row accumulation in one dimension.

Used by the SSM/hybrid architectures (Mamba2's d_conv=4 short conv in zamba2;
RWKV6's 2-tap token shift).  Structure mirrors ``conv2d``: the (causally
padded) sequence block is VMEM-resident and re-read for each tap (feedback
path), taps accumulate serially into an fp32 scratch (output-stationary), and
channel tiles stream through the grid (paired-SRAM double-buffering).

x: (B, T, C), w: (FL, C)  ->  (B, T, C);  out[t] = sum_r x[t-FL+1+r] * w[r].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BC = 512   # channel tile


def _conv1d_kernel(x_ref, w_ref, o_ref, acc_ref, *, fl: int):
    """grid = (B, C/bc). x_ref: (1, T+FL-1, bc); w_ref: (fl, bc)."""
    t = o_ref.shape[1]
    x = x_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for r in range(fl):                      # serial tap accumulation
        acc_ref[...] += (x[r:r + t, :].astype(jnp.float32)
                         * w_ref[r, :].astype(jnp.float32)[None, :])
    o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def conv1d_causal(x: jnp.ndarray, w: jnp.ndarray, *, bc: int = BC,
                  interpret: bool) -> jnp.ndarray:
    b, t, c = x.shape
    fl, c2 = w.shape
    assert c == c2, (x.shape, w.shape)
    bc = min(bc, c)
    cpad = (-c) % bc
    xp = jnp.pad(x, ((0, 0), (fl - 1, 0), (0, cpad)))   # causal left-pad
    wp = jnp.pad(w, ((0, 0), (0, cpad)))
    n_c = (c + cpad) // bc

    out = pl.pallas_call(
        functools.partial(_conv1d_kernel, fl=fl),
        grid=(b, n_c),
        in_specs=[
            pl.BlockSpec((1, t + fl - 1, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((fl, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, t, bc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, t, c + cpad), x.dtype),
        scratch_shapes=[pltpu.VMEM((t, bc), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[..., :c]
