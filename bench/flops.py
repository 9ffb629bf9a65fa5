"""Operation counts computed from shapes: the work an algorithm needs,
never what a compiler happened to emit.

* :func:`dense_lm_train_flops_per_token` — forward and backward of a dense
  decoder (GQA attention, SwiGLU MLP, tied or untied head) per training
  token, with no recomputation counted.
"""
from __future__ import annotations


def dense_lm_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product once per token: the
    attention projections and MLP of every layer plus the LM head (the
    embedding gather does no arithmetic; a tied head still multiplies)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    return cfg["num_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def dense_lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 FLOPs per matmul weight per token (2 forward, 4 backward) plus the
    attention scores and the weighted sum over all ``seq_len`` keys
    (2·2·seq·heads·head_dim forward per layer, times 3 with the backward).
    The causal mask is not discounted: the program computes the full score
    matrix, as the usual MFU convention counts it."""
    attn_fwd = 4 * seq_len * cfg["num_heads"] * cfg["head_dim"]
    return 6.0 * dense_lm_matmul_params(cfg) + 3.0 * cfg["num_layers"] * attn_fwd
