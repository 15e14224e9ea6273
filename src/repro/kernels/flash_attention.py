"""Fused causal flash attention (prefill/train) — Pallas TPU.

Completes the kernel family: conv2d/matmul (the paper's conv modes),
decode_attention (§III.C serving), and this kernel for the prefill/train
shapes.  CARLA mapping: the query block is the *resident* operand in VMEM;
KV blocks *stream*; the running (m, l, acc) softmax state is the partial
result living on-chip until the sweep completes (the paper's wide-SRAM
accumulators).  Score blocks never touch HBM — this is the structural fix
for the memory-bound train/prefill cells measured in §Roofline.

q: (B, T, H, dh); k, v: (B, S, Kh, dh) -> (B, T, H, dh).
Grid: (B, Kh, T/bq, S/bk) — KV innermost (the streamed reduction); the
causal mask skips block compute via pl.when where the whole block is masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
BQ, BK = 256, 256


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_k: int, scale: float, window: int,
                  softcap: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skip: kv block strictly after the q block contributes 0
    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        q = q_ref[0, 0]                            # (bq, G, dh) resident
        k = k_ref[0, 0]                            # (bk, dh)
        v = v_ref[0, 0]
        g, dh = q.shape[1], q.shape[2]
        sc = jnp.einsum("qgd,sd->gqs", q, k,
                        preferred_element_type=jnp.float32) * scale
        if softcap and softcap > 0:
            sc = softcap * jnp.tanh(sc / softcap)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        if window and window > 0:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        sc = jnp.where(ok[None], sc, NEG_INF)

        m_prev = m_ref[...]                        # (G, bq)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
            "gqs,sd->gqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0, 0] = jnp.swapaxes(out, 0, 1).astype(o_ref.dtype)  # (bq,G,dh)


def flash_attention_fused(q, k, v, *, window: int = 0, softcap: float = 0.0,
                          bq: int = BQ, bk: int = BK,
                          interpret: bool):
    """Fused causal GQA attention.  q: (B,T,H,dh); k/v: (B,S,Kh,dh)."""
    b, t, h, dh = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq, bk = min(bq, t), min(bk, s)
    assert t % bq == 0 and s % bk == 0, (t, s, bq, bk)

    qb = jnp.swapaxes(q.reshape(b, t, kh, g, dh), 1, 2)   # (B,Kh,T,G,dh)
    kb = jnp.swapaxes(k, 1, 2)                            # (B,Kh,S,dh)
    vb = jnp.swapaxes(v, 1, 2)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=s // bk,
                          scale=dh ** -0.5, window=window, softcap=softcap),
        grid=(b, kh, t // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, g, dh),
                         lambda ib, ik, iq, is_: (ib, ik, iq, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda ib, ik, iq, is_: (ib, ik, is_, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda ib, ik, iq, is_: (ib, ik, is_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, g, dh),
                               lambda ib, ik, iq, is_: (ib, ik, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, t, g, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, dh), jnp.float32),
                        pltpu.VMEM((g, bq), jnp.float32),
                        pltpu.VMEM((g, bq), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb)
    return jnp.swapaxes(out, 1, 2).reshape(b, t, h, dh)
