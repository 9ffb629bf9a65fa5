"""Metric functions for the engine's streaming diagnostics buffer.

A metrics function has signature ``(state, batches) -> {name: array}`` —
``batches`` is the round's K-stacked training data (so train-side metrics
see exactly what the optimizer saw), every value is a fixed-shape array
(scalars or small vectors like per-group losses), and the whole dict is one
row of the fixed-size on-device buffer the engine fills inside ``lax.scan``.

Builders here cover the two problem families in the repo; custom callers
(e.g. ``examples/adversarial_training.py``) write their own inline.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kgt_minimax as kgt
from repro.core import mixing as mixing_lib
from repro.core.minimax import MinimaxProblem


def _consensus_block(state) -> Dict[str, jnp.ndarray]:
    """The state-health metrics every run wants: consensus Ξx/Ξy, the
    Lemma-8 ‖c̄‖ watchdogs for both corrections, and the ȳ norm
    (``correction_mean_norm`` is exactly the client-mean L2 norm, applied
    here to y)."""
    return {
        "consensus_x": mixing_lib.consensus_error(state.x),
        "consensus_y": mixing_lib.consensus_error(state.y),
        "corr_x_norm": kgt.correction_mean_norm(state.cx),
        "corr_y_norm": kgt.correction_mean_norm(state.cy),
        "y_bar_norm": kgt.correction_mean_norm(state.y),
    }


def dro_metrics_fn(
    problem: MinimaxProblem,
    model_cfg: ModelConfig,
    *,
    num_groups: int,
    eval_batch: Optional[Any] = None,
    compute_dtype=jnp.bfloat16,
):
    """Metrics for DRO-LM training (what ``repro.launch.train`` logs).

    Train-side: f(x̄, ȳ) and the mean per-group loss on the round's own
    first (k=0, client 0) batch, and the round's own counters where the
    state carries them (``KGTState.counters``: a sorted-dispatch MoE's
    ``moe_rows_held`` and ``moe_load_max``, over all clients and local
    steps).  Eval-side (when ``eval_batch`` is given —
    a fixed held-out batch from ``repro.engine.sampler.held_out_eval_batch``):
    mean and per-group losses of the consensus model on data the optimizer
    never trains on.
    """
    from repro.models import per_group_loss

    def metrics(state, batches) -> Dict[str, jnp.ndarray]:
        xbar = kgt.mean_over_clients(state.x)
        ybar = state.y.mean(0)
        train_b = jax.tree.map(lambda b: b[0, 0], batches)  # (k=0, client 0)
        train_losses, _, _ = per_group_loss(
            xbar, train_b, model_cfg, num_groups=num_groups,
            compute_dtype=compute_dtype)
        out = {
            "f_bar": problem.value(xbar, ybar, train_b, None),
            "mean_loss": train_losses.mean(),
            **_consensus_block(state),
            **(state.counters or {}),
        }
        if eval_batch is not None:
            eval_losses, _, _ = per_group_loss(
                xbar, eval_batch, model_cfg, num_groups=num_groups,
                compute_dtype=compute_dtype)
            out["eval_loss"] = eval_losses.mean()
            out["eval_group_loss"] = eval_losses  # (G,) vector row
        return out

    return metrics


def quadratic_metrics_fn(problem: MinimaxProblem):
    """Metrics for the synthetic NC-SC quadratic: the exact ‖∇Φ(x̄)‖ oracle
    the theory-validation benchmarks track, plus the consensus block."""

    def metrics(state, batches) -> Dict[str, jnp.ndarray]:
        del batches
        xbar = kgt.mean_over_clients(state.x)
        return {
            "phi_grad_norm": problem.phi_grad_norm(xbar),
            **_consensus_block(state),
        }

    return metrics
