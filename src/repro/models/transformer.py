"""Composable decoder stack.

A model is a sequence of *blocks* (see ``ModelConfig.blocks()``).  Layers are
grouped into repeated *units* (the arch's block pattern) whose parameters are
stacked along a leading repeat dim and executed with ``lax.scan`` — keeping the
HLO O(pattern) instead of O(layers) for 80-layer configs.  A remainder segment
(when num_layers % pattern != 0) is its own smaller stack.

Block kinds:
  attn / sliding         GQA attention (+ optional window) + SwiGLU MLP
  attn_local             windowed attention (RecurrentGemma local layer) + MLP
  moe                    GQA attention + MoE MLP
  ssm                    Mamba2 SSD mixer (norm + mixer residual only)
  rglru                  RG-LRU temporal mixer + MLP
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import context as dist_ctx
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import init_mlp, mlp, rms_norm

Cache = Any


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------

def segments(cfg: ModelConfig) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """((unit_kinds, n_repeats), ...) covering cfg.blocks()."""
    blocks = cfg.blocks()
    pat = cfg.block_pattern or None
    if pat is None:
        if cfg.arch_type == "hybrid":
            pat = cfg.rglru.block_pattern
        elif cfg.arch_type == "moe":
            pat = ("moe",)
        elif cfg.arch_type == "ssm":
            pat = ("ssm",)
        else:
            pat = (blocks[0],)
    n_full = len(blocks) // len(pat)
    rem = blocks[n_full * len(pat):]
    segs = []
    if n_full:
        segs.append((tuple(pat), n_full))
    if rem:
        segs.append((tuple(rem), 1))
    return tuple(segs)


# ---------------------------------------------------------------------------
# Per-block init / forward
# ---------------------------------------------------------------------------

def init_block(key, kind: str, cfg: ModelConfig):
    d = cfg.d_model
    keys = jax.random.split(key, 4)
    if kind == "ssm":
        return {"norm1": jnp.zeros((d,)), "ssm": ssm_lib.init_ssm(keys[0], cfg, d)}
    if kind == "rglru":
        return {
            "norm1": jnp.zeros((d,)),
            "rglru": rglru_lib.init_rglru(keys[0], cfg, d),
            "norm2": jnp.zeros((d,)),
            "mlp": init_mlp(keys[1], d, cfg.d_ff),
        }
    p = {
        "norm1": jnp.zeros((d,)),
        "attn": attn_lib.init_attention(keys[0], cfg, d),
        "norm2": jnp.zeros((d,)),
    }
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(keys[1], cfg, d)
    else:
        p["mlp"] = init_mlp(keys[1], d, cfg.d_ff)
    return p


def _attn_window(kind: str, cfg: ModelConfig) -> int:
    if kind == "sliding":
        return cfg.sliding_window
    if kind == "attn_local":
        return cfg.rglru.local_window
    if cfg.long_context_window:  # long_500k variant for full-attn archs
        return cfg.long_context_window
    return 0


def _residual(cfg: ModelConfig, y):
    """A block's branch as Granite scales it (no operation by default)."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def block_forward(
    kind: str,
    params,
    x,
    cfg: ModelConfig,
    *,
    mode: str,            # "train" | "prefill" | "decode"
    positions,            # (B,S) absolute positions
    cache: Optional[Dict] = None,
    pos=None,             # scalar decode position
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
):
    """Returns (x_out, new_cache, aux_loss, stats): ``stats`` are the
    routing statistics of a sorted-dispatch MoE block, else None."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, params["norm1"], cfg.norm_eps)

    if kind == "ssm":
        conv_s = cache["conv"] if cache else None
        ssd_s = cache["state"] if cache else None
        y, new_cache = ssm_lib.ssm_forward(
            params["ssm"], h, cfg, compute_dtype, conv_s, ssd_s,
            decode=(mode == "decode"),
        )
        return x + y, new_cache, aux, None

    if kind == "rglru":
        conv_s = cache["conv"] if cache else None
        h_s = cache["h"] if cache else None
        y, new_cache = rglru_lib.rglru_forward(
            params["rglru"], h, cfg, compute_dtype, conv_s, h_s,
            decode=(mode == "decode"),
        )
        x = x + y
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, compute_dtype)
        return x, new_cache, aux, None

    # attention-family blocks -------------------------------------------------
    window = _attn_window(kind, cfg)
    scale = cfg.attention_multiplier or None
    q, k, v = attn_lib.qkv_project(params["attn"], h, cfg, positions, compute_dtype)
    q = dist_ctx.apply("attn_qkv", q)  # optional head-sharding switch

    if mode == "decode":
        assert cache is not None
        kc, vc = cache["k"], cache["v"]
        c_len = kc.shape[1]
        # write position: ring for windowed caches, absolute otherwise.
        # One-hot masked write instead of dynamic-update-slice: elementwise
        # select preserves a seq-sharded cache layout under GSPMD (a DUS on a
        # sharded dim triggers involuntary full rematerialization).
        widx = (pos % c_len) if window else jnp.minimum(pos, c_len - 1)
        onehot = (jnp.arange(c_len, dtype=jnp.int32) == widx)[None, :, None, None]
        kc = jnp.where(onehot, k.astype(kc.dtype), kc)
        vc = jnp.where(onehot, v.astype(vc.dtype), vc)
        # cold-start validity: slots <= pos written so far (ring: all-true
        # once pos >= window, which is exactly when wrapping starts)
        valid = jnp.arange(c_len, dtype=jnp.int32) <= pos
        ctx = attn_lib.decode_attention(q, kc.astype(compute_dtype),
                                        vc.astype(compute_dtype), valid,
                                        scale=scale)
        new_cache = {"k": kc, "v": vc}
    else:
        new_cache = None
        if mode == "prefill" or attn_impl == "qchunk":
            ctx = attn_lib.qchunk_attention(q, k, v, window=window,
                                            scale=scale)
        else:
            ctx = attn_lib.naive_attention(q, k, v, window=window,
                                           scale=scale)
        if cache is not None:  # prefill populating a cache
            c_len = cache["k"].shape[1]
            kw = k[:, -c_len:].astype(cache["k"].dtype)
            vw = v[:, -c_len:].astype(cache["v"].dtype)
            new_cache = {"k": kw, "v": vw}

    ctx = dist_ctx.apply("attn_out", ctx)  # back to seq-sharding
    y = attn_lib.out_project(params["attn"], ctx, compute_dtype)
    x = x + _residual(cfg, y)

    h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
    stats = None
    if kind == "moe" and cfg.moe.dispatch == "sorted":
        y2, aux, stats = moe_lib.moe_mlp_sorted(params["moe"], h2, cfg,
                                                compute_dtype)
    elif kind == "moe":
        y2, aux = moe_lib.moe_mlp(params["moe"], h2, cfg, compute_dtype)
    else:
        y2 = mlp(params["mlp"], h2, compute_dtype)
    return x + _residual(cfg, y2), new_cache, aux, stats


# ---------------------------------------------------------------------------
# Stacked segments
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig):
    """Params: tuple of segment stacks; each stack is a tuple (per block in
    unit) of param pytrees stacked along a leading repeat dim."""
    segs = segments(cfg)
    out = []
    for si, (unit, reps) in enumerate(segs):
        unit_stacks = []
        for bi, kind in enumerate(unit):
            ks = jax.random.split(jax.random.fold_in(key, si * 97 + bi), reps)
            ps = [init_block(ks[r], kind, cfg) for r in range(reps)]
            unit_stacks.append(jax.tree.map(lambda *a: jnp.stack(a), *ps))
        out.append(tuple(unit_stacks))
    return tuple(out)


def stack_forward(
    stack_params,
    x,
    cfg: ModelConfig,
    *,
    mode: str,
    positions,
    caches=None,
    pos=None,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    remat: bool = False,
):
    """Run all segments.  caches mirrors stack_params structure (or None).
    Returns (x, new_caches, total_aux, stats): ``stats`` are the routing
    statistics of the sorted-dispatch MoE layers, concatenated over layers
    ({} for a model without them)."""
    segs = segments(cfg)
    new_caches = []
    layer_stats = []
    total_aux = jnp.zeros((), jnp.float32)

    for si, (unit, reps) in enumerate(segs):
        seg_params = stack_params[si]
        seg_cache = caches[si] if caches is not None else None

        def unit_fn(carry, xs, unit=unit):
            xx, aux = carry
            p_slices, c_slices = xs
            new_cs, unit_stats = [], []
            for bi, kind in enumerate(unit):
                c = c_slices[bi] if c_slices is not None else None
                xx, nc, a, st = block_forward(
                    kind, p_slices[bi], xx, cfg, mode=mode, positions=positions,
                    cache=c, pos=pos, compute_dtype=compute_dtype,
                    attn_impl=attn_impl,
                )
                new_cs.append(nc)
                if st is not None:
                    unit_stats.append(st)
            xx = dist_ctx.apply_residual(xx)
            return (xx, aux + a), (tuple(new_cs), tuple(unit_stats))

        f = jax.checkpoint(unit_fn) if (remat and mode == "train") else unit_fn
        xs = (seg_params, seg_cache)
        (x, total_aux), (seg_new_cache, seg_stats) = jax.lax.scan(
            f, (x, total_aux), xs)
        new_caches.append(seg_new_cache)
        layer_stats.extend(seg_stats)

    stats = (jax.tree.map(lambda *t: jnp.concatenate(t), *layer_stats)
             if layer_stats else {})
    return (x, (tuple(new_caches) if caches is not None else None),
            total_aux, stats)
