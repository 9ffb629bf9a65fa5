"""Top-level language model: embeddings -> stacked decoder -> head, plus the
per-group loss used by the DRO minimax objective, KV/state cache management,
and the decode step.

Modality frontends (VLM vision encoder, audio EnCodec) are stubs per the
brief: batches carry precomputed ``prefix`` embeddings (VLM) or multi-codebook
token streams (audio); only the transformer backbone is real.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import context as dist_ctx
from repro.models import transformer as tf
from repro.models.layers import embed_init, rms_norm


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    k_embed, k_stack, k_head = jax.random.split(key, 3)
    if cfg.num_codebooks:
        embed = embed_init(k_embed, (cfg.num_codebooks, cfg.vocab_size, cfg.d_model))
    else:
        embed = embed_init(k_embed, (cfg.vocab_size, cfg.d_model))
    params = {
        "embed": embed,
        "stack": tf.init_stack(k_stack, cfg),
        "final_norm": jnp.zeros((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            params["head"] = embed_init(
                k_head, (cfg.num_codebooks, cfg.d_model, cfg.vocab_size))
        else:
            params["head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size))
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig, compute_dtype):
    emb = params["embed"].astype(compute_dtype)
    if cfg.num_codebooks:
        # tokens: (B,S,ncb) -> sum of per-codebook embeddings
        parts = [emb[c][tokens[..., c]] for c in range(cfg.num_codebooks)]
        return sum(parts)
    x = emb[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def lm_head(params, x, cfg: ModelConfig, compute_dtype):
    logits = _head(params, x, cfg, compute_dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits.astype(jnp.float32) / cfg.logits_scaling
    return logits


def _head(params, x, cfg: ModelConfig, compute_dtype):
    if cfg.tie_embeddings:
        w = params["embed"].astype(compute_dtype)
        if cfg.num_codebooks:
            return jnp.einsum("bsd,cvd->bscv", x, w)
        return jnp.einsum("bsd,vd->bsv", x, w)
    w = params["head"].astype(compute_dtype)
    if cfg.num_codebooks:
        return jnp.einsum("bsd,cdv->bscv", x, w)
    return jnp.einsum("bsd,dv->bsv", x, w)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def backbone(
    params,
    batch: Dict[str, Any],
    cfg: ModelConfig,
    *,
    mode: str = "train",
    compute_dtype=jnp.bfloat16,
    caches=None,
    pos=None,
    remat: bool = False,
):
    """Everything up to (and incl.) the final norm.  Returns (hidden (B,S,d),
    new_caches, aux, stats), ``stats`` the MoE layers' routing statistics
    (``transformer.stack_forward``)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg, compute_dtype)
    if "embed_bias" in batch:  # adversarial objective: universal perturbation
        x = x + batch["embed_bias"].astype(compute_dtype)
    b, s = x.shape[0], x.shape[1]
    offset = 0
    if cfg.num_prefix_tokens and "prefix" in batch and mode != "decode":
        prefix = batch["prefix"].astype(compute_dtype)
        x = jnp.concatenate([prefix, x], axis=1)
        offset = prefix.shape[1]
    if mode == "decode":
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :].repeat(b, 0)

    x, new_caches, aux, stats = tf.stack_forward(
        params["stack"], x, cfg, mode=mode, positions=positions, caches=caches,
        pos=pos, compute_dtype=compute_dtype, remat=remat,
        attn_impl="qchunk" if mode == "prefill" else "auto",
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if offset:
        x = x[:, offset:]
    return x, new_caches, aux, stats


def forward(
    params,
    batch: Dict[str, Any],
    cfg: ModelConfig,
    *,
    mode: str = "train",
    compute_dtype=jnp.bfloat16,
    caches=None,
    pos=None,
    remat: bool = False,
    last_only: bool = False,
):
    """Returns (logits, new_caches, aux).  ``last_only`` computes the head on
    the final position only (prefill servers)."""
    x, new_caches, aux, _ = backbone(
        params, batch, cfg, mode=mode, compute_dtype=compute_dtype,
        caches=caches, pos=pos, remat=remat)
    if last_only:
        x = x[:, -1:]
    logits = lm_head(params, x, cfg, compute_dtype)
    return logits, new_caches, aux


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def token_losses(logits, labels):
    """Per-token cross-entropy in float32.  logits: (B,S,V) or (B,S,C,V)."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if nll.ndim == 3:  # multi-codebook: mean over codebooks
        nll = nll.mean(-1)
    return nll  # (B,S)


def chunked_nll(params, hidden, labels, cfg: ModelConfig, *,
                compute_dtype=jnp.bfloat16, chunk: int = 512):
    """Fused cross-entropy: head matmul + CE per sequence chunk inside a
    rematerialized scan, so full (B,S,V) logits are never resident (the
    big-vocab memory fix; bwd recomputes each chunk's logits)."""
    b, s, d = hidden.shape
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))) if pad else hidden
    lab_w = [(0, 0), (0, pad)] + [(0, 0)] * (labels.ndim - 2)
    lab = jnp.pad(labels, lab_w) if pad else labels
    h = h.reshape(b, nc, c, d).swapaxes(0, 1)          # (nc, B, c, d)
    lab = lab.reshape(b, nc, c, *labels.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def one(carry, xs):
        hc, lc = xs
        logits = lm_head(params, hc, cfg, compute_dtype)
        return carry, token_losses(logits, lc)

    _, nll = jax.lax.scan(one, (), (h, lab))
    nll = nll.swapaxes(0, 1).reshape(b, nc * c)
    return nll[:, :s]


def per_group_loss(params, batch, cfg: ModelConfig, *, num_groups: int,
                   compute_dtype=jnp.bfloat16, remat: bool = False):
    """Group-resolved LM loss for DRO.  batch needs "labels" (B,S[,ncb]) and
    "groups" (B,S) int32 in [0, num_groups).  Returns ((G,) losses, aux,
    stats), ``stats`` the MoE routing statistics ({} without them)."""
    hidden, _, aux, stats = backbone(
        params, batch, cfg, mode="train", compute_dtype=compute_dtype,
        remat=remat)
    nll = chunked_nll(params, hidden, batch["labels"], cfg,
                      compute_dtype=compute_dtype)  # (B,S)
    g = batch["groups"]
    onehot = jax.nn.one_hot(g, num_groups, dtype=jnp.float32)  # (B,S,G)
    sums = jnp.einsum("bs,bsg->g", nll, onehot)
    counts = jnp.maximum(onehot.sum((0, 1)), 1.0)
    return sums / counts, aux, stats


def lm_loss(params, batch, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
            remat: bool = False):
    hidden, _, aux, _ = backbone(
        params, batch, cfg, mode="train", compute_dtype=compute_dtype, remat=remat)
    nll = chunked_nll(params, hidden, batch["labels"], cfg,
                      compute_dtype=compute_dtype)
    return nll.mean() + aux, aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _block_cache_shape(kind: str, cfg: ModelConfig, batch: int, seq_len: int,
                       dtype):
    hd = cfg.resolved_head_dim
    if kind == "ssm":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nheads = d_in // s.d_head
        conv_ch = d_in + 2 * s.d_state
        return {
            "conv": jnp.zeros((batch, s.d_conv - 1, conv_ch), dtype),
            "state": jnp.zeros((batch, nheads, s.d_head, s.d_state), jnp.float32),
        }
    if kind == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        return {
            "conv": jnp.zeros((batch, cfg.rglru.conv_width - 1, w), dtype),
            "h": jnp.zeros((batch, w), jnp.float32),
        }
    # attention-family: cache length = window if windowed else full seq
    window = tf._attn_window(kind, cfg)
    length = min(window, seq_len) if window else seq_len
    return {
        "k": jnp.zeros((batch, length, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, length, cfg.num_kv_heads, hd), dtype),
    }


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """Cache pytree mirroring the stacked segment structure."""
    caches = []
    for unit, reps in tf.segments(cfg):
        unit_caches = []
        for kind in unit:
            one = _block_cache_shape(kind, cfg, batch, seq_len, dtype)
            unit_caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (reps, *x.shape)), one))
        caches.append(tuple(unit_caches))
    return tuple(caches)


def decode_step(params, caches, tokens, pos, cfg: ModelConfig, *,
                compute_dtype=jnp.bfloat16):
    """One-token decode.  tokens: (B,1[,ncb]); pos: scalar int32 absolute
    position.  Returns (logits (B,1,V...), new_caches)."""
    logits, new_caches, _ = forward(
        params, {"tokens": tokens}, cfg, mode="decode",
        compute_dtype=compute_dtype, caches=caches, pos=pos)
    return logits, new_caches
