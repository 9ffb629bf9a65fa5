"""Algorithm-level tests of K-GT-Minimax (Algorithm 1) and baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AlgorithmConfig
from repro.core import (
    KGTState,
    diagnostics,
    init_state,
    make_quadratic_data,
    make_round_step,
    mean_over_clients,
    mixing_matrix,
    quadratic_problem,
)


def _setup(n=8, K=4, sigma=0.0, heterogeneity=1.0, topology="ring", algo="kgt_minimax",
           eta_cx=0.01, eta_cy=0.1, eta_s=0.5, mixing_impl="dense",
           clients_sharded=False):
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, n, dx=10, dy=5, heterogeneity=heterogeneity)
    prob = quadratic_problem(data, sigma=sigma)
    cfg = AlgorithmConfig(algorithm=algo, num_clients=n, local_steps=K,
                          eta_cx=eta_cx, eta_cy=eta_cy, eta_sx=eta_s, eta_sy=eta_s,
                          topology=topology, mixing_impl=mixing_impl)
    client_batch = {k: v for k, v in data.items() if k != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (K, *v.shape)), client_batch)
    st = init_state(prob, cfg, key, init_batch=client_batch,
                    init_keys=jax.random.split(key, n))
    step = jax.jit(make_round_step(prob, cfg,
                                   clients_sharded=clients_sharded))
    return prob, cfg, st, step, kb


def _run(st, step, kb, K, n, rounds, seed=7):
    for t in range(rounds):
        keys = jax.random.split(jax.random.PRNGKey(seed + t), K * n).reshape(K, n, 2)
        st = step(st, kb, keys)
    return st


def test_correction_mean_stays_zero():
    """Lemma 8: the averaged correction is exactly zero in every round."""
    prob, cfg, st, step, kb = _setup(sigma=0.3)
    for t in range(10):
        keys = jax.random.split(jax.random.PRNGKey(t), 4 * 8).reshape(4, 8, 2)
        st = step(st, kb, keys)
        mean_c = jax.tree.leaves(jax.tree.map(lambda c: c.mean(0), st.cx))[0]
        assert float(jnp.abs(mean_c).max()) < 1e-4


def test_converges_on_heterogeneous_ncsc():
    prob, cfg, st, step, kb = _setup(sigma=0.1, heterogeneity=2.0)
    st = _run(st, step, kb, 4, 8, 300)
    d = diagnostics(prob, st)
    assert float(d["phi_grad_norm"]) < 0.15
    assert float(d["consensus_x"]) < 1e-3
    # Lemma 8 watchdogs for BOTH corrections (cy reported since PR 3)
    assert float(d["correction_mean_norm"]) < 1e-3
    assert float(d["correction_mean_norm_y"]) < 1e-3


def test_fully_connected_k1_equals_centralized_sgda():
    """With W = J and K = 1 the average iterate follows centralized SGDA
    exactly (deterministic oracle)."""
    n, K = 4, 1
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, n, dx=6, dy=3)
    prob = quadratic_problem(data, sigma=0.0)
    eta_x, eta_y, eta_s = 0.02, 0.1, 1.0
    cfg = AlgorithmConfig(algorithm="kgt_minimax", num_clients=n, local_steps=K,
                          eta_cx=eta_x, eta_cy=eta_y, eta_sx=eta_s, eta_sy=eta_s,
                          topology="full")
    client_batch = {k: v for k, v in data.items() if k != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (K, *v.shape)), client_batch)
    st = init_state(prob, cfg, key, init_batch=client_batch,
                    init_keys=jax.random.split(key, n))
    step = jax.jit(make_round_step(prob, cfg))

    x_c = mean_over_clients(st.x)
    y_c = mean_over_clients(st.y)
    for t in range(20):
        keys = jax.random.split(jax.random.PRNGKey(t), K * n).reshape(K, n, 2)
        st = step(st, kb, keys)
        gx, gy = prob.full_grads(x_c, y_c)
        x_c = x_c - eta_x * gx
        y_c = y_c + eta_y * gy
    np.testing.assert_allclose(mean_over_clients(st.x), x_c, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mean_over_clients(st.y), y_c, rtol=1e-4, atol=1e-5)


def test_tracking_beats_local_sgda_under_heterogeneity():
    """V3: with strong heterogeneity and local steps, gradient tracking reaches
    a far more stationary point than plain local SGDA at equal budgets."""
    res = {}
    for algo in ("kgt_minimax", "local_sgda"):
        prob, cfg, st, step, kb = _setup(
            sigma=0.0, heterogeneity=3.0, algo=algo, K=8,
            eta_cx=0.01, eta_cy=0.1, eta_s=0.5 if algo == "kgt_minimax" else 1.0)
        st = _run(st, step, kb, 8, 8, 200)
        res[algo] = float(diagnostics(prob, st)["phi_grad_norm"])
    assert res["kgt_minimax"] < 0.15
    assert res["kgt_minimax"] < 0.05 * res["local_sgda"]


@pytest.mark.parametrize("algo", ["dsgda", "local_sgda", "gt_gda"])
def test_baselines_run_and_stay_finite(algo):
    prob, cfg, st, step, kb = _setup(algo=algo, sigma=0.1, eta_cx=0.005,
                                     eta_cy=0.05, K=4)
    st = _run(st, step, kb, 4, 8, 50)
    for leaf in jax.tree.leaves(st.x):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("topo", ["ring", "exp", "torus", "full"])
def test_shift_lowered_round_matches_dense(topo):
    """With the clients axis split across devices, the dense gossip of a W
    that leaves some client shift unweighed (ring, exp, torus) is rolls
    over the shifts it weighs, and of the complete graph one contraction,
    and the parameter gossip is formed at the round's start.  Run on one
    device, that round is the same algorithm as the unsharded one."""
    from repro.core import mixing

    n = 9 if topo == "torus" else 8
    shifts = len(mixing._client_shifts(mixing_matrix(topo, n)))
    assert (shifts == n) == (topo == "full")
    outs = []
    for sharded in (False, True):
        prob, cfg, st, step, kb = _setup(n=n, sigma=0.3, topology=topo,
                                         clients_sharded=sharded)
        outs.append(_run(st, step, kb, 4, n, 30))
    for name in ("x", "y", "cx", "cy"):
        for a, b in zip(jax.tree.leaves(getattr(outs[0], name)),
                        jax.tree.leaves(getattr(outs[1], name))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def doubly_stochastic_w(n: int, seed: int) -> np.ndarray:
    """Random symmetric doubly-stochastic W (symmetrized Sinkhorn), beyond
    the named topologies.  Shared with the hypothesis suite in
    test_property.py."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.1, 1.0, (n, n))
    a = a + a.T + n * np.eye(n)
    for _ in range(200):
        a = a / a.sum(1, keepdims=True)
        a = (a + a.T) / 2
    assert np.allclose(a.sum(1), 1.0, atol=1e-9) and np.allclose(a, a.T)
    return a


def check_round_mean_dynamics(algo, n, k, seed, mixing_impl="dense"):
    """One round_step under any doubly-stochastic W: the client mean of x/y
    evolves exactly as under W = J (mixing preserves the mean), and Lemma 8's
    Σ_i c_i = 0 invariant holds."""
    w = doubly_stochastic_w(n, seed)
    key = jax.random.PRNGKey(seed)
    data = make_quadratic_data(key, n, dx=5, dy=3, heterogeneity=2.0)
    prob = quadratic_problem(data, sigma=0.0)
    cfg = AlgorithmConfig(algorithm=algo, num_clients=n, local_steps=k,
                          eta_cx=0.01, eta_cy=0.05, eta_sx=0.4, eta_sy=0.4,
                          mixing_impl=mixing_impl, gossip_backend="xla")
    cb = {kk: v for kk, v in data.items() if kk != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (k, *v.shape)), cb)
    st = init_state(prob, cfg, key, init_batch=cb,
                    init_keys=jax.random.split(key, n))
    step_w = make_round_step(prob, cfg, w)
    step_j = make_round_step(prob, cfg, np.full((n, n), 1.0 / n))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), k * n).reshape(k, n, 2)
    st_w = step_w(st, kb, keys)
    st_j = step_j(st, kb, keys)
    np.testing.assert_allclose(mean_over_clients(st_w.x),
                               mean_over_clients(st_j.x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(mean_over_clients(st_w.y),
                               mean_over_clients(st_j.y),
                               rtol=1e-5, atol=1e-5)
    for c in (st_w.cx, st_w.cy):
        mean_c = jax.tree.leaves(jax.tree.map(lambda v: v.mean(0), c))[0]
        assert float(jnp.abs(mean_c).max()) < 1e-4


@pytest.mark.parametrize("algo", ["kgt_minimax", "dsgda", "local_sgda", "gt_gda"])
@pytest.mark.parametrize("mixing_impl", ["dense", "pallas_packed",
                                         "sparse_packed"])
def test_round_mean_dynamics_under_random_doubly_stochastic_w(algo, mixing_impl):
    """Deterministic cousin of the hypothesis property in test_property.py
    (which runs everywhere since the bundled fallback landed)."""
    check_round_mean_dynamics(algo, n=6, k=3, seed=11, mixing_impl=mixing_impl)


def check_participation_invariants(algo, n, k, seed, mask_bits,
                                   mixing_impl="dense", rounds=2):
    """Round steps with traced W + a participation mask (mask_bits: client i
    active iff bit i set): the client-mean dynamics are W-independent (the
    masked W stays doubly stochastic, so x̄ moves by η_s·mean(masked Δ)
    whatever W was drawn), Σ_i c_i stays 0 under ANY mask, and inactive
    clients' (θ, c) are frozen bit-exactly."""
    from repro.core import sparse_topology as sparse
    from repro.core import stochastic_topology as stoch

    mask = jnp.asarray([(mask_bits >> i) & 1 == 1 for i in range(n)])
    w = doubly_stochastic_w(n, seed)
    key = jax.random.PRNGKey(seed)
    data = make_quadratic_data(key, n, dx=5, dy=3, heterogeneity=2.0)
    prob = quadratic_problem(data, sigma=0.0)
    cfg = AlgorithmConfig(algorithm=algo, num_clients=n, local_steps=k,
                          eta_cx=0.01, eta_cy=0.05, eta_sx=0.4, eta_sy=0.4,
                          mixing_impl=mixing_impl, gossip_backend="xla")
    cb = {kk: v for kk, v in data.items() if kk != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (k, *v.shape)), cb)
    st = init_state(prob, cfg, key, init_batch=cb,
                    init_keys=jax.random.split(key, n))
    step = jax.jit(make_round_step(prob, cfg, traced_w=True,
                                   participation=True))
    # the sparse_packed traced-W operand is a SparseTopology pytree; the
    # dense Ws here are fully connected, so from_dense keeps every edge
    bridge = (sparse.from_dense if mixing_impl == "sparse_packed"
              else lambda a: jnp.asarray(a, jnp.float32))
    w_t = bridge(np.asarray(w, np.float32))
    w_j = bridge(np.full((n, n), 1.0 / n, np.float32))
    st_w = st
    inactive = ~np.asarray(mask)
    for t in range(rounds):
        keys = jax.random.split(jax.random.PRNGKey(seed + t),
                                k * n).reshape(k, n, 2)
        prev_w = st_w
        st_w = step(st_w, kb, keys, w_t, mask)
        if t == 0:
            # W-independence of the mean is a ONE-round property from a
            # common state (after a round the per-client spread differs, so
            # later local gradients do too): x̄ must move exactly as under
            # W = J masked by the same participation pattern
            st_j = step(prev_w, kb, keys, w_j, mask)
            np.testing.assert_allclose(mean_over_clients(st_w.x),
                                       mean_over_clients(st_j.x),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(mean_over_clients(st_w.y),
                                       mean_over_clients(st_j.y),
                                       rtol=1e-5, atol=1e-5)
        # inactive clients frozen bit-exactly, every round
        for name in ("x", "y", "cx", "cy"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_w, name))[inactive],
                np.asarray(getattr(prev_w, name))[inactive], err_msg=name)
        for c in (st_w.cx, st_w.cy):
            mean_c = jax.tree.leaves(jax.tree.map(lambda v: v.mean(0), c))[0]
            assert float(jnp.abs(mean_c).max()) < 1e-4


@pytest.mark.parametrize("algo", ["kgt_minimax", "dsgda", "local_sgda", "gt_gda"])
@pytest.mark.parametrize("mixing_impl", ["dense", "pallas_packed",
                                         "sparse_packed"])
def test_participation_invariants_all_variants(algo, mixing_impl):
    """Deterministic cousin of the participation hypothesis properties in
    test_property.py: a mask dropping clients 1 and 3 of 6."""
    check_participation_invariants(algo, n=6, k=3, seed=5,
                                   mask_bits=0b110101, mixing_impl=mixing_impl)


def test_participation_all_inactive_freezes_everything():
    """The degenerate all-clients-down round is a global no-op (bit-exact),
    except the round counter advances."""
    n, k = 4, 2
    key = jax.random.PRNGKey(3)
    data = make_quadratic_data(key, n, dx=4, dy=2)
    prob = quadratic_problem(data, sigma=0.0)
    cfg = AlgorithmConfig(num_clients=n, local_steps=k, eta_cx=0.01,
                          eta_cy=0.05)
    cb = {kk: v for kk, v in data.items() if kk != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (k, *v.shape)), cb)
    st = init_state(prob, cfg, key, init_batch=cb,
                    init_keys=jax.random.split(key, n))
    step = jax.jit(make_round_step(prob, cfg, participation=True))
    keys = jax.random.split(jax.random.PRNGKey(0), k * n).reshape(k, n, 2)
    out = step(st, kb, keys, jnp.zeros((n,), bool))
    for name in ("x", "y", "cx", "cy"):
        for a, b in zip(jax.tree.leaves(getattr(out, name)),
                        jax.tree.leaves(getattr(st, name))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out.round) == int(st.round) + 1


def test_round_step_extras_arity_validated():
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, 4, dx=4, dy=2)
    prob = quadratic_problem(data, sigma=0.0)
    cfg = AlgorithmConfig(num_clients=4, local_steps=2)
    cb = {kk: v for kk, v in data.items() if kk != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (2, *v.shape)), cb)
    st = init_state(prob, cfg, key)
    step = make_round_step(prob, cfg, traced_w=True)
    keys = jax.random.split(key, 2 * 4).reshape(2, 4, 2)
    with pytest.raises(TypeError, match="extra operand"):
        step(st, kb, keys)  # missing the traced W


def test_make_round_step_validates_mixing_impl():
    """mixing_impl is validated on every branch — including topology_cycle
    and the traced-W and participation paths."""
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, 4, dx=4, dy=2)
    prob = quadratic_problem(data, sigma=0.0)
    for cfg in (
        AlgorithmConfig(num_clients=4, mixing_impl="bogus"),
        AlgorithmConfig(num_clients=4, mixing_impl="bogus",
                        topology_cycle=("ring", "full")),
        AlgorithmConfig(num_clients=4, mixing_impl="ring",
                        topology_cycle=("ring", "full")),
        AlgorithmConfig(num_clients=4, mixing_impl="fused_ring",
                        topology="exp"),
    ):
        with pytest.raises(ValueError, match="unknown mixing_impl"):
            make_round_step(prob, cfg)
    # the retired names are refused on the churn paths too, and traced_w
    # fights a topology_cycle
    with pytest.raises(ValueError, match="unknown mixing_impl"):
        make_round_step(prob, AlgorithmConfig(num_clients=4,
                                              mixing_impl="ring"),
                        traced_w=True)
    with pytest.raises(ValueError, match="unknown mixing_impl"):
        make_round_step(prob, AlgorithmConfig(num_clients=4,
                                              mixing_impl="fused_ring"),
                        participation=True)
    with pytest.raises(ValueError, match="topology_cycle"):
        make_round_step(prob, AlgorithmConfig(num_clients=4,
                                              topology_cycle=("ring", "full")),
                        traced_w=True)


def test_consensus_reached_from_identical_init():
    prob, cfg, st, step, kb = _setup(sigma=0.0, heterogeneity=0.0)
    st = _run(st, step, kb, 4, 8, 100)
    d = diagnostics(prob, st)
    assert float(d["consensus_x"]) < 1e-5
