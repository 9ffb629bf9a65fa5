"""granite-moe-1b-a400m-ep4 — one chip's share of granite-moe-1b-a400m.

Deployment: every layer divided over 4 chips (experts, heads and the
vocabulary), the 24 layers in 3 pipeline stages of 8.  This chip holds
shard 0 of stage 0: 8 of the 32 experts, 4 of the 16 query heads and 2 of
the 8 KV heads, and 12,288 of the 49,155 vocabulary rows.  Every width is
the published one.  The multipliers are Granite's as recalled from its
``config.json``; the benchmark's configuration file says which of them a
published Granite config confirms (``logits_scaling`` is assumed).
[hf:ibm-granite/granite-3.0-1b-a400m-base]
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-1b-a400m-ep4",
    arch_type="moe",
    num_layers=8,
    d_model=1024,
    num_heads=4,
    num_kv_heads=2,
    head_dim=64,
    d_ff=512,
    vocab_size=12288,
    rope_theta=10000.0,
    norm_eps=1e-6,
    moe=MoEConfig(num_experts=32, top_k=8, expert_d_ff=512,
                  router_aux_coef=0.01, dispatch="sorted", experts_held=8),
    tie_embeddings=True,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
    source="hf:ibm-granite/granite-3.0-1b-a400m-base",
)
