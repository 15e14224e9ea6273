"""Fused decode attention — CARLA §III.C weight-stationary mode for serving.

One query token attends to a long KV cache.  The CARLA insight maps exactly:
the tiny operand (the query) is *resident*; the big operand (the cache)
*streams through once*; partial results (running max / sum / weighted
accumulator) stay in VMEM scratch until the block sweep finishes — the
paper's Eq (11) property ("each filter weight is only fetched once") becomes
"each cache line is fetched exactly once per token".

This removes the XLA-level decode bottleneck measured in §Perf cell C: the
unfused score chain (scores -> mask -> softmax -> weighted sum) makes ~5
HBM passes over score-sized tensors; the fused kernel makes one pass over
the cache and none over scores (they never leave VMEM).

q: (B, H, dh); cache k/v: (B, S, Kh, dh); pos: (B,) int32 -> out (B, H, dh).
Grid: (B, Kh, S/bs) with the S axis innermost (the streamed reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
BS = 512   # cache block (streamed)


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, bs: int, n_s: int,
                        scale: float):
    """q_ref: (1, G, dh) resident; k/v_ref: (1, bs, dh) streamed blocks."""
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                # (G, dh) resident
    k = k_ref[0, 0]                                # (bs, dh)
    v = v_ref[0, 0]
    sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G,bs)

    pos = pos_ref[0]
    kpos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    sc = jnp.where(kpos <= pos, sc, NEG_INF)       # causal vs cache

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    p = jnp.exp(sc - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, pos: jnp.ndarray, *,
                     bs: int = BS, interpret: bool) -> jnp.ndarray:
    """q: (B, H, dh); cache: (B, S, Kh, dh); pos: (B,) -> (B, H, dh)."""
    b, h, dh = q.shape
    _, s, kh, _ = cache_k.shape
    g = h // kh
    bs = min(bs, s)
    spad = (-s) % bs
    if spad:
        cache_k = jnp.pad(cache_k, ((0, 0), (0, spad), (0, 0), (0, 0)))
        cache_v = jnp.pad(cache_v, ((0, 0), (0, spad), (0, 0), (0, 0)))
    n_s = (s + spad) // bs
    qg = q.reshape(b, kh, g, dh)
    # (B, S, Kh, dh) -> (B, Kh, S, dh) so the block walks S contiguously
    kt = jnp.swapaxes(cache_k, 1, 2)
    vt = jnp.swapaxes(cache_v, 1, 2)

    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, bs=bs, n_s=n_s,
                          scale=dh ** -0.5),
        grid=(b, kh, n_s),
        in_specs=[
            pl.BlockSpec((1,), lambda ib, ik, is_: (ib,)),          # pos
            pl.BlockSpec((1, 1, g, dh), lambda ib, ik, is_: (ib, ik, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda ib, ik, is_: (ib, ik, is_, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda ib, ik, is_: (ib, ik, is_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda ib, ik, is_: (ib, ik, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, dh), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32)],
        interpret=interpret,
    )(pos, qg, kt, vt)
    return out.reshape(b, h, dh)
