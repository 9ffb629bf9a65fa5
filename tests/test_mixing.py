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
    """f32 gossip is f32 on every backend.  Unrolled (n <= 8), every
    product and sum is an f32 elementwise op, which no backend rounds to
    bf16; as a contraction (n = 9) it asks for HIGHEST precision, which a
    TPU needs (its default rounds f32 operands to bf16, which would round
    every parameter to bf16 each round)."""
    import re

    for n in (4, 9):
        w = topology.mixing_matrix("ring", n)
        x = {"a": jnp.ones((n, 3), jnp.float32)}
        txt = jax.jit(lambda t: mixing.mix_dense(t, w)).lower(x).as_text()
        assert "bf16" not in txt
        if n <= mixing.UNROLL_MAX_CLIENTS:
            ops = re.findall(r"stablehlo\.(multiply|add) .*: (tensor<\S+>)",
                             txt)
            assert len(ops) == 2 * n - 1
            assert all(t.endswith("xf32>") for _, t in ops)
        else:
            assert "HIGHEST" in txt


# ---------------------------------------------------------------------------
# the dense gossip's two forms: unrolled client slices up to
# UNROLL_MAX_CLIENTS, one contraction above
# ---------------------------------------------------------------------------

SIZES = [1, 2, 4, 8, 9]
GOSSIP_DTYPES = [jnp.float32, jnp.bfloat16]
W_KINDS = ["static_ring", "traced_random"]
# (rtol, atol) against a float64 W @ x of the un-rounded inputs
DENSE_TOL = {jnp.float32: (1e-6, 1e-6), jnp.bfloat16: (2e-2, 2e-2)}


def _uniform_tree(n, seed):
    rng = np.random.default_rng(seed)
    return {"a": rng.uniform(-1, 1, (n,)).astype(np.float32),
            "b": rng.uniform(-1, 1, (n, 5)).astype(np.float32),
            "c": {"d": rng.uniform(-1, 1, (n, 3, 130)).astype(np.float32)}}


def _doubly_stochastic(kind, n):
    if kind == "static_ring":
        return np.asarray(topology.mixing_matrix("ring", n), np.float32)
    # a convex combination of permutation matrices
    rng = np.random.default_rng(100 + n)
    coef = rng.dirichlet(np.ones(3))
    return sum(c * np.eye(n)[rng.permutation(n)]
               for c in coef).astype(np.float32)


def _dense_fn(kind, w, gossip_dtype):
    """(call, lower) of jit(tree -> W @ tree), W a constant or traced."""
    gd = None if gossip_dtype == jnp.float32 else gossip_dtype
    if kind == "static_ring":
        fn = jax.jit(lambda t: mixing.mix_dense(t, w, gossip_dtype=gd))
        return fn, fn.lower
    fn = jax.jit(lambda t, wt: mixing.mix_dense(t, wt, gossip_dtype=gd))
    return (lambda t: fn(t, w)), (lambda t: fn.lower(t, w))


@pytest.mark.parametrize("w_kind", W_KINDS)
@pytest.mark.parametrize("gossip_dtype", GOSSIP_DTYPES)
@pytest.mark.parametrize("n", SIZES)
def test_mix_dense_matches_float64(n, gossip_dtype, w_kind):
    w = _doubly_stochastic(w_kind, n)
    tree = _uniform_tree(n, n)
    call, _ = _dense_fn(w_kind, w, gossip_dtype)
    out = call(tree)
    rtol, atol = DENSE_TOL[gossip_dtype]
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        ref = np.einsum("ij,j...->i...", w.astype(np.float64),
                        x.astype(np.float64))
        assert y.dtype == jnp.float32 and y.shape == x.shape
        np.testing.assert_allclose(np.asarray(y), ref, rtol=rtol, atol=atol)


@pytest.mark.parametrize("w_kind", W_KINDS)
@pytest.mark.parametrize("gossip_dtype", GOSSIP_DTYPES)
@pytest.mark.parametrize("n", SIZES)
def test_mix_dense_preserves_client_mean(n, gossip_dtype, w_kind):
    w = _doubly_stochastic(w_kind, n)
    tree = _uniform_tree(n, 10 + n)
    call, _ = _dense_fn(w_kind, w, gossip_dtype)
    out = call(tree)
    rtol, atol = ((1e-5, 1e-6) if gossip_dtype == jnp.float32
                  else DENSE_TOL[gossip_dtype])
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(y).mean(0), x.mean(0),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("w_kind", W_KINDS)
@pytest.mark.parametrize("gossip_dtype", GOSSIP_DTYPES)
@pytest.mark.parametrize("n", SIZES)
def test_mix_dense_contracts_clients_only_above_unroll_limit(
        n, gossip_dtype, w_kind):
    """Up to UNROLL_MAX_CLIENTS the lowered gossip has no dot_general (so
    nothing asks for a client-minor layout); above it, one per leaf."""
    w = _doubly_stochastic(w_kind, n)
    tree = _uniform_tree(n, 0)
    _, lower = _dense_fn(w_kind, w, gossip_dtype)
    txt = lower(tree).as_text()
    n_dots = txt.count("stablehlo.dot_general")
    if n <= mixing.UNROLL_MAX_CLIENTS:
        assert n_dots == 0
    else:
        assert n_dots == len(jax.tree.leaves(tree))
        assert "HIGHEST" in txt


@pytest.mark.parametrize("n", [2, 4, 8])
def test_mix_dense_sharded_keeps_the_contraction(n):
    """For a client axis split across devices, a W that weighs every client
    shift (the complete graph; the ring at n = 2) is one einsum at every n,
    equal to the unrolled form to f32 rounding."""
    w = np.asarray(topology.mixing_matrix("full" if n > 2 else "ring", n),
                   np.float32)
    tree = _uniform_tree(n, 3)
    txt = jax.jit(lambda t: mixing.mix_dense_sharded(t, w)).lower(
        tree).as_text()
    assert txt.count("stablehlo.dot_general") == len(jax.tree.leaves(tree))
    for a, b in zip(jax.tree.leaves(mixing.mix_dense_sharded(tree, w)),
                    jax.tree.leaves(mixing.mix_dense(tree, w))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("topo,n,shifts", [
    ("ring", 4, 3), ("ring", 8, 3), ("torus", 9, 7)])
def test_mix_dense_sharded_rolls_the_weighed_shifts(topo, n, shifts):
    """A static W with a zero circulant diagonal is the sum of the leaf
    rolled by each client shift that W weighs: no contraction, one roll per
    shift but the self one, equal to the unrolled form to f32 rounding; a
    traced W keeps the einsum."""
    w = np.asarray(topology.mixing_matrix(topo, n), np.float32)
    assert len(mixing._client_shifts(w)) == shifts
    tree = _uniform_tree(n, 3)
    txt = jax.jit(lambda t: mixing.mix_dense_sharded(t, w)).lower(
        tree).as_text()
    assert "stablehlo.dot_general" not in txt
    for a, b in zip(jax.tree.leaves(mixing.mix_dense_sharded(tree, w)),
                    jax.tree.leaves(mixing.mix_dense(tree, w))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    traced = jax.jit(mixing.mix_dense_sharded).lower(tree, w).as_text()
    assert traced.count("stablehlo.dot_general") == len(jax.tree.leaves(tree))


_SHARDED_GOSSIP = """
import os, re, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import mixing, topology
mesh = jax.make_mesh((4,), ("clients",))
tree = {"a": jnp.ones((4, 256), jnp.float32),
        "b": jnp.ones((4, 8, 128), jnp.float32)}
shard = NamedSharding(mesh, P("clients"))
out = {}
for topo in ("full", "ring"):
    w = topology.mixing_matrix(topo, 4)
    for sharded in (False, True):
        mix = mixing.make_mixer(topo, "dense", w, clients_sharded=sharded)
        txt = jax.jit(mix, in_shardings=shard, out_shardings=shard).lower(
            tree).compile().as_text()
        out[topo + str(sharded)] = {
            op: len(re.findall(r"= \\S+ " + op + r"(?:-start)?\\(", txt))
            for op in ("all-gather", "collective-permute", "all-reduce")}
print(json.dumps(out))
"""


def _sharded_gossip_counts() -> dict:
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _SHARDED_GOSSIP], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_clients_gossip_is_one_all_gather_per_leaf():
    """On a 4-device clients axis, a W that weighs every client shift
    lowers to one all-gather per leaf; slicing the sharded axis would take
    n - 1 permutes.  The ring, which weighs two neighbors, takes one
    permute per neighbor and leaf: 2 of the all-gather's 3 slices in."""
    counts = _sharded_gossip_counts()
    assert counts["fullTrue"] == {"all-gather": 2, "collective-permute": 0,
                                  "all-reduce": 0}
    assert counts["ringTrue"] == {"all-gather": 0, "collective-permute": 4,
                                  "all-reduce": 0}
    assert counts["fullFalse"]["collective-permute"] > 2
