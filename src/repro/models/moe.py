"""Mixture-of-Experts FFN with capacity-bounded einsum dispatch.

GShard-style: top-k routing -> one-hot dispatch/combine tensors -> batched
expert FFNs.  The dispatch is a dense einsum (MXU-friendly, collective-light:
under expert-parallel sharding XLA lowers it to an all-to-all on the capacity
buffer), compute is bounded by ``E * capacity ~= top_k * tokens * cf``.

Supports top-1 (llama4-style, + optional always-on shared expert) and top-2
(mixtral).  Experts are SwiGLU FFNs with weights stacked on a leading expert
axis so the whole module shards with one spec: experts over the data axis
(EP), d_ff over the model axis (TP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import perf

from .sharding_hints import BATCH, constrain


def moe_init(key, n_experts: int, d: int, d_ff: int):
    k1, k2, k3, kr = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, d_ff ** -0.5
    return {
        "wi": jax.random.normal(k1, (n_experts, d, d_ff), jnp.float32) * s_in,
        "wg": jax.random.normal(k2, (n_experts, d, d_ff), jnp.float32) * s_in,
        "wo": jax.random.normal(k3, (n_experts, d_ff, d), jnp.float32) * s_out,
        "router": jax.random.normal(kr, (d, n_experts), jnp.float32) * s_in,
    }


def _group_for_shards(x, t: int):
    """B3 (§Perf): split T into per-'model'-shard blocks so routing capacity
    and the dispatch/combine contractions are shard-local."""
    ms = jax.sharding.get_abstract_mesh().shape.get("model", 1)
    if perf.get().grouped_moe_dispatch and ms > 1 and t % ms == 0 \
            and t >= 2 * ms:
        return ms
    return 1


def moe_ffn(params, x, *, top_k: int, capacity_factor: float = 1.25):
    """x: (B, T, d) -> (B, T, d), plus aux load-balancing loss.

    GShard grouping: groups are (batch row x model-shard token block), so
    capacity bookkeeping (cumsum) and the dispatch/combine einsums contract
    over *local* tokens; the (B, S, T/S, E, C) buffers shard like the
    activations and no partial-sum all-reduce is needed (B3, §Perf).
    """
    b, t_full, d = x.shape
    e = params["router"].shape[-1]
    s = _group_for_shards(x, t_full)
    if s > 1:
        y, aux = _moe_grouped(params, x.reshape(b, s, t_full // s, d),
                              top_k=top_k, capacity_factor=capacity_factor)
        return y.reshape(b, t_full, d), aux
    return _moe_flat(params, x, top_k=top_k, capacity_factor=capacity_factor)


def _moe_grouped(params, x, *, top_k: int, capacity_factor: float):
    """x: (B, S, Tl, d) with S = model shards; all routing shard-local."""
    b, s, tl, d = x.shape
    e = params["router"].shape[-1]
    x = constrain(x, (BATCH, "model", None, None))

    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (B,S,Tl,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    capacity = max(1, int(capacity_factor * top_k * tl / e))
    comb_dt = x.dtype if perf.get().bf16_moe_dispatch else jnp.float32

    combine = jnp.zeros((b, s, tl, e, capacity), comb_dt)
    base = jnp.zeros((b, s, 1, e), jnp.float32)
    for j in range(top_k):
        sel = jax.nn.one_hot(gate_idx[..., j], e, dtype=jnp.float32)
        pos_in_e = (jnp.cumsum(sel, axis=2) - 1.0 + base) * sel
        keep = pos_in_e < capacity
        pos_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), capacity,
                                dtype=comb_dt) * (sel * keep).astype(
                                    comb_dt)[..., None]
        combine = combine + (gate_vals[..., j, None, None].astype(comb_dt)
                             * pos_oh)
        base = base + jnp.sum(sel, axis=2, keepdims=True)
    combine = constrain(combine, (BATCH, "model", None, None, None))
    dispatch = (combine > 0).astype(x.dtype)

    # EP when experts divide 'data' (tokens travel to expert owners via one
    # all-to-all); otherwise expert compute stays token-sharded.
    data_sz = jax.sharding.get_abstract_mesh().shape.get("data", 1)
    ep_ok = data_sz > 1 and e % data_sz == 0
    ep = (None, "model", "data", None, None) if ep_ok else \
        (BATCH, "model", None, None, None)

    xe = constrain(jnp.einsum("bstec,bstd->bsecd", dispatch, x), ep)
    h = jnp.einsum("bsecd,edf->bsecf", xe, params["wi"].astype(x.dtype))
    g = jnp.einsum("bsecd,edf->bsecf", xe, params["wg"].astype(x.dtype))
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * h
    ye = constrain(jnp.einsum("bsecf,efd->bsecd", h,
                              params["wo"].astype(x.dtype)), ep)
    y = jnp.einsum("bstec,bsecd->bstd", combine.astype(x.dtype), ye)
    y = constrain(y, (BATCH, "model", None, None))

    me = jnp.mean(probs, axis=(0, 1, 2))
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1, 2))
    aux = e * jnp.sum(me * ce)
    return y, aux


def _moe_flat(params, x, *, top_k: int, capacity_factor: float):
    b, t, d = x.shape
    e = params["router"].shape[-1]

    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (B, T, E)

    # top-k gates, renormalized
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (B, T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    capacity = max(1, int(capacity_factor * top_k * t / e))

    # B1 (§Perf): the (B,T,E,C) dispatch/combine tensors dominate MoE-layer
    # HBM + collective traffic; bf16 storage halves both (routing/position
    # math stays fp32).
    comb_dt = x.dtype if perf.get().bf16_moe_dispatch else jnp.float32

    # position of each (token, choice) within its expert's per-group buffer;
    # later choices offset by all earlier choices' per-expert counts so
    # buffer slots never collide across the k routing rounds (GShard).
    combine = jnp.zeros((b, t, e, capacity), comb_dt)
    base = jnp.zeros((b, 1, e), jnp.float32)
    for j in range(top_k):                                     # static, k<=2
        sel = jax.nn.one_hot(gate_idx[..., j], e, dtype=jnp.float32)  # (B,T,E)
        pos_in_e = (jnp.cumsum(sel, axis=1) - 1.0 + base) * sel       # (B,T,E)
        keep = pos_in_e < capacity                                    # drop overflow
        pos_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), capacity,
                                dtype=comb_dt) * (sel * keep).astype(
                                    comb_dt)[..., None]
        combine = combine + (gate_vals[..., j, None, None].astype(comb_dt)
                             * pos_oh)
        base = base + jnp.sum(sel, axis=1, keepdims=True)

    dispatch = (combine > 0).astype(x.dtype)                   # (B, T, E, C)

    # Expert parallelism: experts over 'data' when E divides it, otherwise
    # the capacity axis shards over 'data' (expert-data parallelism); d_ff
    # over 'model' (TP).  The dispatch einsum reshards token-sharded -> EP
    # (GSPMD lowers it to the MoE all-to-all).
    data_sz = jax.sharding.get_abstract_mesh().shape.get("data", 1)
    ep = (None, "data", None, None) if (data_sz > 1 and e % data_sz == 0) \
        else (None, None, BATCH, None)
    # dispatch -> expert buffers: (B, E, C, d)
    xe = constrain(jnp.einsum("btec,btd->becd", dispatch, x), ep)
    # batched SwiGLU experts
    h = jnp.einsum("becd,edf->becf", xe, params["wi"].astype(x.dtype))
    h = constrain(h, ep[:3] + ("model",))
    g = jnp.einsum("becd,edf->becf", xe, params["wg"].astype(x.dtype))
    g = constrain(g, ep[:3] + ("model",))
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * h
    ye = constrain(jnp.einsum("becf,efd->becd", h,
                              params["wo"].astype(x.dtype)), ep)
    # combine back: (B, T, d)
    y = jnp.einsum("btec,becd->btd", combine.astype(x.dtype), ye)
    y = constrain(y, (BATCH, "model", None))

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=(0, 1))                          # mean router prob
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    return y, aux
