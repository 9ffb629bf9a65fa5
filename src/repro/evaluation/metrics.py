"""Evaluation metrics for decentralized DRO training.

Worst-group loss is the quantity DRO optimizes implicitly (the y-ascent
soft-maximizes hard groups); per-group perplexity exposes the robustness
the paper's minimax formulation buys over ERM.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_lib


def group_metrics(params, batch, cfg: ModelConfig, *, num_groups: int,
                  compute_dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Per-group CE/ppl + worst-group stats on one batch."""
    losses, _, _ = model_lib.per_group_loss(
        params, batch, cfg, num_groups=num_groups, compute_dtype=compute_dtype)
    present = jax.nn.one_hot(batch["groups"], num_groups).sum((0, 1)) > 0
    masked = jnp.where(present, losses, -jnp.inf)
    worst = jnp.max(masked)
    return {
        "group_loss": losses,
        "group_ppl": jnp.exp(jnp.clip(losses, 0, 20.0)),
        "mean_loss": jnp.where(present, losses, 0.0).sum() / jnp.maximum(
            present.sum(), 1),
        "worst_group_loss": worst,
        "worst_group": jnp.argmax(masked),
        "groups_present": present.sum(),
    }


def evaluate_clients(state_x, dm, cfg: ModelConfig, key, *, num_groups: int,
                     per_client_batch: int = 4, seq_len: int = 128,
                     compute_dtype=jnp.bfloat16) -> Dict[str, float]:
    """Evaluate the consensus model x̄ on every client's distribution —
    the federated metric that matters (robustness across clients)."""
    from repro.data import synthetic as data_lib

    xbar = jax.tree.map(lambda x: x.mean(0), state_x)
    n = dm.mixtures.shape[0]
    worst_client = -jnp.inf
    means = []
    for i in range(n):
        b = data_lib.sample_client_batch(
            dm, jax.random.fold_in(key, i), i, per_client_batch, seq_len,
            cfg.num_codebooks)
        m = group_metrics(xbar, b, cfg, num_groups=num_groups,
                          compute_dtype=compute_dtype)
        means.append(m["mean_loss"])
        worst_client = jnp.maximum(worst_client, m["mean_loss"])
    return {
        "client_mean_loss": float(jnp.stack(means).mean()),
        "worst_client_loss": float(worst_client),
    }
