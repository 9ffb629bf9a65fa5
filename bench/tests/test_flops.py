"""FLOP counts from shapes."""
import jax
import jax.numpy as jnp
import pytest

import flops


PAPER_TOY = {"num_layers": 12, "d_model": 768, "num_heads": 12,
             "num_kv_heads": 4, "head_dim": 64, "d_ff": 2048,
             "vocab_size": 32000}


def test_paper_toy_flops_per_token():
    # per layer 768·768 (q) + 2·768·256 (k, v) + 768·768 (o) + 3·768·2048
    assert flops.dense_lm_matmul_params(PAPER_TOY) == (
        12 * 6_291_456 + 32000 * 768)
    per_token = flops.dense_lm_train_flops_per_token(PAPER_TOY, 512)
    attention = 3 * 12 * 4 * 512 * 12 * 64
    assert per_token == 6 * 100_073_472 + attention
    assert per_token == pytest.approx(6.57e8, rel=1e-3)


def test_flops_per_token_match_the_compiled_step_at_reduced_width():
    """The program's per-client DRO gradient at the smoke-test width,
    compiled here, against the count.  The fused cross-entropy
    rematerializes the LM head in the backward, but under ``grad`` the
    loss value is dead, so XLA drops the forward copy: the compiled step
    does each product once, as the count has it."""
    from repro.analysis import hlo_cost
    from repro.configs import registry
    from repro.core import objectives

    cfg = registry.reduced(registry.get_model_config("paper-toy"))
    b, s, groups = 2, 32, 8
    problem = objectives.dro_problem(cfg, num_groups=groups)
    x = jax.eval_shape(problem.init_x, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "groups": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    y = jax.ShapeDtypeStruct((groups,), jnp.float32)
    text = jax.jit(lambda x, y, bt: problem.grads(x, y, bt, None)).lower(
        x, y, batch).compile().as_text()
    measured = hlo_cost.analyze(text).dot_flops
    shape = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
             "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
             "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
             "vocab_size": cfg.vocab_size}
    counted = flops.dense_lm_train_flops_per_token(shape, s) * b * s
    # what remains is the group-loss contraction, 2·G FLOPs a token each way
    assert measured == pytest.approx(counted, rel=1e-3)
