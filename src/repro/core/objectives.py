"""Concrete NC-SC minimax objectives.

* ``quadratic_problem`` — synthetic NC-SC with closed-form Φ and ∇Φ; the
  workhorse for validating the paper's theory (V1–V6 in DESIGN.md).
* ``dro_problem`` — distributionally-robust LM training over G token groups;
  y ∈ R^G, f_i(x,y) = Σ_g y_g ℓ_g(x; D_i) − μ/2‖y‖²  (linear in y ⇒ μ-SC).
* ``adversarial_problem`` — universal adversarial embedding perturbation;
  y ∈ R^{d_model}, f_i(x,y) = ℓ(x; E+y) − μ/2‖y‖².
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.minimax import MinimaxProblem


# ---------------------------------------------------------------------------
# Synthetic quadratic NC-SC (exact oracles)
# ---------------------------------------------------------------------------

def make_quadratic_data(
    key,
    n_clients: int,
    dx: int = 10,
    dy: int = 5,
    mu: float = 1.0,
    l_smooth: float = 4.0,
    heterogeneity: float = 1.0,
    nonconvexity: float = 0.5,
):
    """Per-client data for  f_i(x,y) = ½xᵀA_i x + q_iᵀx + yᵀB_i x + b_iᵀy − μ/2‖y‖².

    A_i symmetric with eigenvalues in [-nonconvexity·L, L·scale] (nonconvex),
    B_i, b_i, q_i heterogeneous with scale ``heterogeneity`` around a shared
    mean.  Returns dict of stacked (n, ...) arrays.
    """
    ks = jax.random.split(key, 6)
    # Global Ā must keep Φ bounded below (Assumption 1): draw it PSD with
    # eigenvalues in [0.1, l_smooth/2].  Per-client nonconvexity/heterogeneity
    # enters through ZERO-MEAN symmetric perturbations E_i (Σ_i E_i = 0), so
    # each f_i is nonconvex in x while the global primal stays bounded.
    q_rot = jnp.linalg.qr(jax.random.normal(ks[0], (dx, dx)))[0]
    eigs = jnp.linspace(0.1, l_smooth / 2, dx)
    base_a = (q_rot * eigs) @ q_rot.T

    e = jax.random.normal(ks[1], (n_clients, dx, dx)) / np.sqrt(dx)
    e = 0.5 * (e + jnp.swapaxes(e, -1, -2))
    e = e - e.mean(0, keepdims=True)  # exactly zero-mean across clients
    a = base_a[None] + (nonconvexity + heterogeneity) * e

    base_b = jax.random.normal(ks[2], (dy, dx)) / np.sqrt(max(dx, dy))
    base_b = base_b * (l_smooth / 2 / jnp.linalg.norm(base_b, 2))
    db = jax.random.normal(ks[3], (n_clients, dy, dx)) / np.sqrt(dx)
    db = db - db.mean(0, keepdims=True)
    b_mat = base_b[None] + heterogeneity * db

    b_vec = jax.random.normal(ks[4], (n_clients, dy)) * heterogeneity
    q_vec = jax.random.normal(ks[5], (n_clients, dx)) * heterogeneity
    return {"A": a, "B": b_mat, "b": b_vec, "q": q_vec, "mu": jnp.float32(mu)}


def quadratic_problem(data: Dict[str, Any], sigma: float = 0.0) -> MinimaxProblem:
    """MinimaxProblem over per-client slices of ``data``.

    The per-client batch is {"A": (dx,dx), "B": (dy,dx), "b": (dy,), "q": (dx,)}
    (one slice).  Stochasticity: additive Gaussian noise of scale sigma on the
    value's linear terms (⇒ unbiased, bounded-variance gradients, Assumption 3).
    """
    mu = float(data["mu"])
    dx = data["A"].shape[-1]
    dy = data["B"].shape[-2]

    a_bar = np.asarray(data["A"].mean(0))
    b_bar = np.asarray(data["B"].mean(0))
    bv_bar = np.asarray(data["b"].mean(0))
    q_bar = np.asarray(data["q"].mean(0))

    def value(x, y, batch, key):
        f = (
            0.5 * x @ (batch["A"] @ x)
            + batch["q"] @ x
            + y @ (batch["B"] @ x)
            + batch["b"] @ y
            - 0.5 * mu * jnp.sum(y * y)
        )
        if sigma > 0.0:
            kx, ky = jax.random.split(key)
            f = f + sigma * (
                jax.random.normal(kx, (dx,)) @ x + jax.random.normal(ky, (dy,)) @ y
            )
        return f

    def phi_grad(x):
        # y*(x) = (B̄x + b̄)/μ ; ∇Φ = Āx + q̄ + B̄ᵀ y*(x)
        ystar = (b_bar @ x + bv_bar) / mu
        return a_bar @ x + q_bar + b_bar.T @ ystar

    def full_grads(x, y):
        gx = a_bar @ x + q_bar + b_bar.T @ y
        gy = b_bar @ x + bv_bar - mu * y
        return gx, gy

    def affine_coeffs(batch, key):
        return _quadratic_affine_coeffs(
            batch, key, mu=mu, dx=dx, dy=dy,
            sigma=(jnp.float32(sigma) if sigma > 0.0 else None))

    return MinimaxProblem(
        init_x=lambda key: jax.random.normal(key, (dx,)),
        init_y=lambda key: jnp.zeros((dy,)),
        value=value,
        phi_grad=phi_grad,
        full_grads=full_grads,
        affine_coeffs=affine_coeffs,
        mu=mu,
    )


def _quadratic_affine_coeffs(batch, key, *, mu, dx, dy, sigma):
    """(G, h) with (∇x f, ∇y f) = split(G z + h) for z = concat(x, y).

        G = [[A, Bᵀ], [B, −μI]]       h = [q; b] (+ σ·noise)

    The noise term reuses the exact key split of ``value`` (kx for x-terms,
    ky for y-terms), so the fused-round path sees the *same* stochastic
    gradients as autodiff through ``value`` — bit-level parity modulo matmul
    reassociation, held to 1e-6 by tests/test_fused_round.py.
    """
    a, b_mat = batch["A"], batch["B"]
    top = jnp.concatenate([a, jnp.swapaxes(b_mat, -1, -2)], axis=-1)
    bottom = jnp.concatenate(
        [b_mat, -jnp.float32(mu) * jnp.eye(dy, dtype=a.dtype)], axis=-1)
    g = jnp.concatenate([top, bottom], axis=-2)
    h = jnp.concatenate([batch["q"], batch["b"]], axis=-1)
    if sigma is not None:
        kx, ky = jax.random.split(key)
        h = h + sigma * jnp.concatenate(
            [jax.random.normal(kx, (dx,)), jax.random.normal(ky, (dy,))])
    return g, h


def quadratic_cell_problem(dx: int, dy: int, mu: float = 1.0,
                           noise: bool = False) -> MinimaxProblem:
    """The quadratic with *all* per-client coefficients read from the batch.

    ``quadratic_problem`` closes over one client-stacked ``data`` dict and a
    static noise scale — one traced program per (data, sigma) point.  A sweep
    cell (``repro.sweep``) instead vmaps a single program over a trajectory
    axis where the data (heterogeneity, seed) and sigma are just array
    leaves, so here they arrive through ``batch``: the per-client slice is
    ``{"A", "B", "b", "q"}`` plus, when ``noise``, a scalar ``"sigma"``.

    The value expression is term-for-term the one in ``quadratic_problem``
    (that is what makes a batched trajectory bit-identical to the same point
    run through the static path).  Whether noise ops exist in the graph is a
    *static* program property — a cell mixing sigma=0 with sigma>0 must be
    split by the grid layer, not multiplied by a traced zero.

    No Φ oracle: the exact ``phi_grad`` needs the client-*mean* coefficients,
    which the sweep runner evaluates itself over its stacked constants.
    """

    def value(x, y, batch, key):
        f = (
            0.5 * x @ (batch["A"] @ x)
            + batch["q"] @ x
            + y @ (batch["B"] @ x)
            + batch["b"] @ y
            - 0.5 * mu * jnp.sum(y * y)
        )
        if noise:
            kx, ky = jax.random.split(key)
            f = f + batch["sigma"] * (
                jax.random.normal(kx, (dx,)) @ x + jax.random.normal(ky, (dy,)) @ y
            )
        return f

    def affine_coeffs(batch, key):
        return _quadratic_affine_coeffs(
            batch, key, mu=mu, dx=dx, dy=dy,
            sigma=(batch["sigma"] if noise else None))

    return MinimaxProblem(
        init_x=lambda key: jax.random.normal(key, (dx,)),
        init_y=lambda key: jnp.zeros((dy,)),
        value=value,
        affine_coeffs=affine_coeffs,
        mu=mu,
    )


# ---------------------------------------------------------------------------
# DRO over a language model
# ---------------------------------------------------------------------------

def dro_problem(cfg: ModelConfig, *, num_groups: int = 8, mu: float = 1.0,
                compute_dtype=jnp.bfloat16, remat: bool = False) -> MinimaxProblem:
    from repro.models import model as model_lib

    def init_x(key):
        return model_lib.init_params(cfg, key)

    def init_y(key):
        return jnp.zeros((num_groups,))

    def value_with_stats(x, y, batch, key):
        del key  # stochasticity comes from the data batch itself
        losses, aux, stats = model_lib.per_group_loss(
            x, batch, cfg, num_groups=num_groups,
            compute_dtype=compute_dtype, remat=remat)
        f = jnp.dot(y, losses) + aux - 0.5 * mu * jnp.sum(y * y)
        return f, stats.get("rows")

    def value(x, y, batch, key):
        return value_with_stats(x, y, batch, key)[0]

    sorted_moe = cfg.moe.dispatch == "sorted" and "moe" in cfg.blocks()
    return MinimaxProblem(init_x=init_x, init_y=init_y, value=value, mu=mu,
                          value_with_stats=value_with_stats,
                          counters=moe_counters if sorted_moe else None,
                          counter_names=MOE_COUNTERS if sorted_moe else ())


#: The per-round counters of a sorted-dispatch MoE model.
MOE_COUNTERS = ("moe_rows_held", "moe_load_max")


def moe_counters(rows) -> Dict[str, Any]:
    """One round's routing counters from the rows routed to each held
    expert, ``(local step, client, layer, held expert)``: the rows routed
    to held experts, and the largest (layer, expert) load summed over the
    round's steps and clients over the mean load."""
    load = jnp.sum(rows, axis=(0, 1)).astype(jnp.float32)
    return {"moe_rows_held": jnp.sum(load),
            "moe_load_max": jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0)}


# ---------------------------------------------------------------------------
# Adversarial embedding perturbation
# ---------------------------------------------------------------------------

def adversarial_problem(cfg: ModelConfig, *, mu: float = 10.0, scale: float = 0.1,
                        compute_dtype=jnp.bfloat16,
                        remat: bool = False) -> MinimaxProblem:
    from repro.models import model as model_lib

    def init_x(key):
        return model_lib.init_params(cfg, key)

    def init_y(key):
        return jnp.zeros((cfg.d_model,))

    def value(x, y, batch, key):
        del key
        perturbed = dict(batch)
        perturbed["embed_bias"] = scale * y
        logits, _, aux = model_lib.forward(
            x, perturbed, cfg, mode="train", compute_dtype=compute_dtype,
            remat=remat)
        nll = model_lib.token_losses(logits, batch["labels"]).mean()
        return nll + aux - 0.5 * mu * jnp.sum(y * y)

    return MinimaxProblem(init_x=init_x, init_y=init_y, value=value, mu=mu)
