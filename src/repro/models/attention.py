"""GQA attention: naive (oracle / small-seq train), q-chunked (long prefill),
decode-over-cache, with optional sliding window.  The Pallas flash kernel in
``repro.kernels`` is the TPU-target implementation of the same math; selection
happens in ``repro.models.transformer`` via the attention ``impl`` knob.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


def init_attention(key, cfg, d_model: int):
    """cfg: ModelConfig (uses num_heads / num_kv_heads / head_dim / qkv_bias)."""
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, (d_model, cfg.num_heads, hd), in_axis=0),
        "wk": dense_init(kk, (d_model, cfg.num_kv_heads, hd), in_axis=0),
        "wv": dense_init(kv, (d_model, cfg.num_kv_heads, hd), in_axis=0),
        "wo": dense_init(ko, (cfg.num_heads, hd, d_model), in_axis=0),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, hd))
        p["bk"] = jnp.zeros((cfg.num_kv_heads, hd))
        p["bv"] = jnp.zeros((cfg.num_kv_heads, hd))
    return p


def qkv_project(params, x, cfg, positions, compute_dtype=jnp.bfloat16):
    w = lambda p: p.astype(compute_dtype)
    q = jnp.einsum("...sd,dhk->...shk", x, w(params["wq"]))
    k = jnp.einsum("...sd,dhk->...shk", x, w(params["wk"]))
    v = jnp.einsum("...sd,dhk->...shk", x, w(params["wv"]))
    if "bq" in params:
        q = q + w(params["bq"])
        k = k + w(params["bk"])
        v = v + w(params["bv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_project(params, ctx, compute_dtype=jnp.bfloat16):
    return jnp.einsum("...shk,hkd->...sd", ctx, params["wo"].astype(compute_dtype))


def _expand_kv(k, n_rep: int):
    """(..., S, KV, D) -> (..., S, KV*n_rep, D) by repeating each kv head."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=-2)


def causal_mask(sq: int, sk: int, q_offset: int = 0, window: int = 0):
    """Boolean (sq, sk) mask: True = attend. Query i at absolute position
    q_offset + i attends keys at absolute positions 0..sk-1 with j <= i and,
    if window > 0, i - j < window."""
    qi = q_offset + jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window > 0:
        m &= kj > qi - window
    return m


def naive_attention(q, k, v, *, window: int = 0, q_offset: int = 0,
                    scale=None):
    """Reference attention.  q: (B,Sq,H,D); k,v: (B,Sk,KV,D); the scores
    are scaled by ``scale`` (default ``D ** -0.5``).

    GQA via grouped einsums — the kv tensors are never materialized at H
    heads (an explicit repeat forces GSPMD to all-gather the expanded kv over
    a seq-sharded mesh axis: 42 GiB/step measured on qwen2 train_4k)."""
    b, sq, h, d = q.shape
    kv = k.shape[-2]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    mask = causal_mask(sq, k.shape[-3], q_offset, window)
    if g == 1:
        # MHA: direct einsum (the grouped form's singleton dim measurably
        # degrades GSPMD sharding decisions)
        logits = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32) * scale
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("...hqk,...khd->...qhd", probs, v)
    qg = q.reshape(b, sq, kv, g, d)
    logits = jnp.einsum("...qhgd,...khd->...hgqk", qg, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("...hgqk,...khd->...qhgd", probs, v)
    return ctx.reshape(b, sq, h, d)


def qchunk_attention(q, k, v, *, window: int = 0, q_chunk: int = 512,
                     scale=None):
    """Memory-bounded attention for long no-grad prefill: lax.map over query
    blocks (scores materialized per block only)."""
    b, s, h, d = q.shape
    qc = min(q_chunk, s)
    nq = -(-s // qc)
    pad = nq * qc - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = q.reshape(b, nq, qc, h, d).transpose(1, 0, 2, 3, 4)  # (nq,B,qc,H,D)

    def one(args):
        i, qi = args
        return naive_attention(qi, k, v, window=window, q_offset=i * qc,
                               scale=scale)

    out = jax.lax.map(one, (jnp.arange(nq), qs))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nq * qc, h, d)
    return out[:, :s]


def decode_attention(q, k_cache, v_cache, valid, scale=None):
    """Single-token decode: q (B,1,H,D) against a cache (B,S,KV,D) with a
    boolean validity mask ``valid`` (S,) — False for slots not yet written
    (cold start).  GQA is handled by grouping q heads — the kv cache is never
    materialized at H heads (GSPMD-friendly: no repeat, contraction stays
    partial over a seq-sharded cache + small all-reduce)."""
    b, one, h, d = q.shape
    kv = k_cache.shape[-2]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    if g == 1:  # MHA: direct form (see naive_attention)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache).astype(jnp.float32) * scale
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)
    qg = q.reshape(b, one, kv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache).astype(jnp.float32) * scale
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_cache)
    return ctx.reshape(b, one, h, d)
