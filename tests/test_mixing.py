import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mixing, topology


def _tree(n, key):
    k1, k2 = jax.random.split(key)
    return {"a": jax.random.normal(k1, (n, 5)),
            "b": {"c": jax.random.normal(k2, (n, 3, 2))}}


@pytest.mark.parametrize("n", [2, 3, 4, 8])
def test_ring_matches_dense(n):
    w = topology.mixing_matrix("ring", n)
    tree = _tree(n, jax.random.PRNGKey(0))
    dense = mixing.mix_dense(tree, w)
    ring = mixing.mix_ring(tree, float(w[0, 0]), float(w[0, 1 % n]))
    for d, r in zip(jax.tree.leaves(dense), jax.tree.leaves(ring)):
        np.testing.assert_allclose(d, r, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", ["ring", "full", "exp"])
def test_mixing_preserves_mean(name):
    n = 8
    w = topology.mixing_matrix(name, n)
    tree = _tree(n, jax.random.PRNGKey(1))
    mixed = mixing.mix_dense(tree, w)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(mixed)):
        np.testing.assert_allclose(a.mean(0), b.mean(0), rtol=1e-5, atol=1e-6)


def test_mixing_contracts_consensus_error():
    n = 8
    w = topology.mixing_matrix("ring", n)
    tree = _tree(n, jax.random.PRNGKey(2))
    e0 = float(mixing.consensus_error(tree))
    e1 = float(mixing.consensus_error(mixing.mix_dense(tree, w)))
    p = topology.spectral_gap(w)
    assert e1 <= (1 - p) * e0 + 1e-6


def test_bf16_gossip_close_to_f32():
    n = 4
    w = topology.mixing_matrix("ring", n)
    tree = _tree(n, jax.random.PRNGKey(3))
    exact = mixing.mix_dense(tree, w)
    approx = mixing.mix_dense(tree, w, gossip_dtype=jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(approx)):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def test_make_mixer_dispatch():
    w = topology.mixing_matrix("ring", 4)
    tree = _tree(4, jax.random.PRNGKey(4))
    for impl in ("dense", "ring", "fused_ring", "pallas_packed"):
        out = mixing.make_mixer("ring", impl, w)(tree)
        np.testing.assert_allclose(
            jax.tree.leaves(out)[0], jax.tree.leaves(mixing.mix_dense(tree, w))[0],
            rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ring", "fused_ring"])
@pytest.mark.parametrize("topo", ["full", "exp", "torus", "star"])
def test_make_mixer_rejects_ring_impl_on_non_ring_topology(impl, topo):
    """Previously this silently fell back to dense — wrong impl, right
    numbers — masking a misconfiguration.  Now it raises."""
    n = 4
    w = topology.mixing_matrix(topo, n)
    with pytest.raises(ValueError, match="ring"):
        mixing.make_mixer(topo, impl, w)


def test_make_mixer_rejects_unknown_impl():
    w = topology.mixing_matrix("ring", 4)
    with pytest.raises(ValueError, match="unknown mixing_impl"):
        mixing.make_mixer("ring", "bogus", w)


def test_mix_packed_matches_per_leaf_dense():
    n = 8
    w = topology.mixing_matrix("exp", n)
    tree = _tree(n, jax.random.PRNGKey(5))
    packed = mixing.mix_packed(tree, w)
    dense = mixing.mix_dense(tree, w)
    for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(dense)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_f32_gossip_contracts_at_full_precision():
    """f32 gossip is f32 on every backend: the contraction asks for HIGHEST
    precision, which a TPU needs (its default rounds f32 operands to bf16,
    which would round every parameter to bf16 each round)."""
    w = topology.mixing_matrix("ring", 4)
    x = {"a": jnp.ones((4, 3), jnp.float32)}
    txt = jax.jit(lambda t: mixing.mix_dense(t, w)).lower(x).as_text()
    assert "HIGHEST" in txt
