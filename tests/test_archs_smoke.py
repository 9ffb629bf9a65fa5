"""Per-assigned-architecture smoke tests (the brief's deliverable f): a
REDUCED same-family variant runs one forward and one K-GT-Minimax train step
on CPU, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import AlgorithmConfig, MinimaxConfig
from repro.configs.registry import ASSIGNED, SHARES, get_model_config, reduced
from repro.core import init_state, make_round_step, objectives
from repro.data import make_data_model, round_batches
from repro.models import forward, init_params, per_group_loss

B, S, G = 2, 32, 4


def _batch(cfg, key):
    if cfg.num_codebooks:
        toks = jax.random.randint(key, (B, S, cfg.num_codebooks), 0, cfg.vocab_size)
    else:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks,
             "groups": jax.random.randint(key, (B, S), 0, G)}
    if cfg.num_prefix_tokens:
        batch["prefix"] = 0.02 * jax.random.normal(
            key, (B, cfg.num_prefix_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED + SHARES)
def test_reduced_forward_shapes_and_finite(arch):
    cfg = reduced(get_model_config(arch))
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)
    logits, _, aux = forward(params, batch, cfg)
    if cfg.num_codebooks:
        assert logits.shape == (B, S, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))
    losses, _, _ = per_group_loss(params, batch, cfg, num_groups=G)
    assert losses.shape == (G,)
    assert bool(jnp.isfinite(losses).all())


@pytest.mark.parametrize("arch", ASSIGNED + SHARES)
def test_reduced_kgt_train_step(arch):
    """One full communication round (K=2 local DRO-minimax steps + tracking +
    gossip) on the reduced variant — no NaNs, consensus finite."""
    cfg = reduced(get_model_config(arch))
    n, K = 2, 2
    algo = AlgorithmConfig(num_clients=n, local_steps=K, eta_cx=1e-3,
                           eta_cy=1e-2, topology="ring")
    problem = objectives.dro_problem(cfg, num_groups=G, mu=1.0)
    key = jax.random.PRNGKey(2)
    dm = make_data_model(key, vocab_size=cfg.vocab_size, num_groups=G,
                         num_clients=n, alpha=0.5)
    batches = round_batches(dm, key, local_steps=K, num_clients=n,
                            per_client_batch=B, seq_len=S, cfg=cfg)
    init_b = jax.tree.map(lambda x: x[0], batches)
    state = init_state(problem, algo, key, init_batch=init_b,
                       init_keys=jax.random.split(key, n))
    step = make_round_step(problem, algo)
    keys = jax.random.split(key, K * n).reshape(K, n, 2)
    new_state = step(state, batches, keys)
    for leaf in jax.tree.leaves(new_state.x):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
    assert int(new_state.round) == 1
    # parameters actually moved
    moved = sum(
        float(jnp.abs(a - b).sum())
        for a, b in zip(jax.tree.leaves(new_state.x), jax.tree.leaves(state.x)))
    assert moved > 0
