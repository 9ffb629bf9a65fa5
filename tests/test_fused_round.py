"""Parity + protocol suite for the whole-round kernel and compressed gossip.

Three contracts:

* ``kernels.ops.fused_round`` (interpret-mode Pallas) matches the
  ``kernels.ref.fused_round_ref`` oracle to ≤1e-6 on arbitrary unaligned
  shapes, for every compress method × gossip dtype.
* ``mixing_impl="fused_round"`` routed through ``make_round_step``
  reproduces the dense per-leaf round across all four algorithm variants,
  lr schedules, stochastic-gradient noise, and churn (sampled W +
  participation masks).
* The error-feedback compression protocol: the residual identity
  ``Q(v) + e = v`` is bit-exact (Sterbenz), the EF state survives an
  engine checkpoint bit-exactly, and 100 compressed rounds stay within a
  tight relative divergence of the exact trajectory.

Cross-lowering trajectories are NOT compared under compression: fused and
pallas_packed compute Δ with ~1e-7 op-order differences that int8
``round()`` amplifies near quantization boundaries — the invariant suite
(Σc = 0, divergence bound, same-lowering parity) is the correct contract.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs.base import AlgorithmConfig
from repro.core import (
    init_state,
    make_quadratic_data,
    make_round_step,
    quadratic_problem,
)
from repro.core import compression, mixing, stochastic_topology as stoch
from repro.core import topology
from repro.kernels import ops
from repro.kernels.quantize import QUANT_METHODS, wire_bits

ALGOS = ("kgt_minimax", "dsgda", "local_sgda", "gt_gda")


# ---------------------------------------------------------------------------
# kernel (interpret) vs oracle, raw operands
# ---------------------------------------------------------------------------

def _kernel_operands(n=6, dz=150, k=3, seed=0):
    """Deliberately unaligned (n % 8 != 0, dz % 128 != 0)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    w = jnp.asarray(topology.mixing_matrix("ring", n), jnp.float32)
    # O(0.1-ish) operands: the contract is ≤1e-6 *absolute*, so keep the
    # matvec reductions (dz- and n-length f32 sums whose op order differs
    # between the kernel and the oracle) from inflating the noise floor
    z0 = jax.random.normal(ks[0], (n, dz), jnp.float32) * 0.3
    c = jax.random.normal(ks[1], (n, dz), jnp.float32) * 0.1
    ef = jax.random.normal(ks[2], (n, dz), jnp.float32) * 0.01
    g = jax.random.normal(ks[3], (n, dz, dz), jnp.float32) * (0.1 / dz)
    h = jax.random.normal(ks[4], (k, n, dz), jnp.float32) * 0.05
    step = jnp.full((n, dz), 0.05, jnp.float32)
    etas = jnp.full((n, dz), 0.5, jnp.float32)
    corr = jnp.broadcast_to(
        jax.random.normal(ks[5], (dz,), jnp.float32) * 0.3, (n, dz))
    mask = jnp.ones((n, dz), jnp.float32)
    return w, z0, c, ef, g, h, step, etas, corr, mask


@pytest.mark.parametrize("gossip_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("compress", [None, "bf16", "int8"])
def test_fused_round_kernel_matches_oracle(compress, gossip_dtype):
    args = _kernel_operands(seed=hash((compress, gossip_dtype)) % 97)
    outs = {}
    for backend in ("interpret", "xla"):
        outs[backend] = ops.fused_round(*args, backend=backend,
                                        compress=compress,
                                        gossip_dtype=gossip_dtype)
    for a, b in zip(outs["interpret"], outs["xla"]):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_fused_round_rejects_oversized_state():
    """VMEM guard: the whole-round kernel holds G = (n, dz, dz) resident,
    so a dz past the scoped-VMEM bound must fail loudly, not silently
    spill."""
    n, dz, k = 4, 1100, 1  # G alone is 40 MiB once padded
    z = jnp.zeros((n, dz))
    with pytest.raises(ValueError, match="fused_round"):
        ops.fused_round(jnp.eye(n), z, z, z, jnp.zeros((n, dz, dz)),
                        jnp.zeros((k, n, dz)), z, jnp.zeros((dz,)),
                        jnp.zeros((dz,)), jnp.ones((n,)),
                        backend="interpret")


# ---------------------------------------------------------------------------
# round_step routing: fused_round vs the dense per-leaf reference
# ---------------------------------------------------------------------------

def _round_setup(algo, impl, backend, n=8, K=4, topo="ring", sigma=0.0,
                 compress=None, lr_scale=None, **mk_kwargs):
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, n, dx=10, dy=5, heterogeneity=2.0)
    prob = quadratic_problem(data, sigma=sigma)
    cfg = AlgorithmConfig(algorithm=algo, num_clients=n, local_steps=K,
                          eta_cx=0.01, eta_cy=0.1, eta_sx=0.5, eta_sy=0.5,
                          topology=topo, mixing_impl=impl,
                          gossip_backend=backend, gossip_compress=compress)
    cb = {k: v for k, v in data.items() if k != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (K, *v.shape)), cb)
    st = init_state(prob, cfg, key, init_batch=cb,
                    init_keys=jax.random.split(key, n))
    step = jax.jit(make_round_step(prob, cfg, lr_scale=lr_scale, **mk_kwargs))
    return st, step, kb, (n, K)


def _run_rounds(algo, impl, backend, rounds=5, **kw):
    st, step, kb, (n, K) = _round_setup(algo, impl, backend, **kw)
    for t in range(rounds):
        keys = jax.random.split(jax.random.PRNGKey(t), K * n).reshape(K, n, 2)
        st = step(st, kb, keys)
    return st


def _assert_state_close(a_state, b_state, atol, msg=""):
    for name in ("x", "y", "cx", "cy"):
        # corrections carry the ±1/(K·η_c) scale (up to 100 at these etas),
        # which amplifies the f32 op-order noise floor by the same factor
        tol = atol * (4 if name in ("cx", "cy") else 1)
        for a, b in zip(jax.tree.leaves(getattr(a_state, name)),
                        jax.tree.leaves(getattr(b_state, name))):
            np.testing.assert_allclose(a, b, rtol=0, atol=tol,
                                       err_msg=f"{msg}{name}")


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("algo", ALGOS)
def test_fused_round_matches_dense_all_variants(algo, backend):
    dense = _run_rounds(algo, "dense", "auto")
    fused = _run_rounds(algo, "fused_round", backend)
    _assert_state_close(dense, fused, 5e-6, msg=f"{algo}/{backend}/")


def test_fused_round_with_noise_matches_dense():
    """σ > 0: the affine oracle must split the noise key exactly like the
    autodiff value path, so identical keys give identical trajectories."""
    dense = _run_rounds("kgt_minimax", "dense", "auto", sigma=0.3)
    fused = _run_rounds("kgt_minimax", "fused_round", "xla", sigma=0.3)
    _assert_state_close(dense, fused, 5e-6)


def test_fused_round_with_lr_schedule():
    sched = lambda r: 1.0 / (1.0 + 0.1 * r.astype(jnp.float32))
    dense = _run_rounds("kgt_minimax", "dense", "auto", lr_scale=sched)
    fused = _run_rounds("kgt_minimax", "fused_round", "interpret",
                        lr_scale=sched)
    _assert_state_close(dense, fused, 5e-6)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("family", ["erdos_renyi", "dropout"])
def test_fused_round_matches_dense_under_churn(family, backend):
    """Sampled W + participation mask as traced operands: the whole-round
    kernel must zero inactive clients' local steps, drop their links, and
    freeze their (θ, c) exactly like the dense round."""
    outs = {}
    for impl, be in (("dense", "auto"), ("fused_round", backend)):
        st, step, kb, (n, K) = _round_setup("kgt_minimax", impl, be, n=8,
                                            topo="full", traced_w=True,
                                            participation=True)
        w_fn = stoch.make_w_sampler(
            family, n, jax.random.PRNGKey(11),
            base_w=topology.mixing_matrix("full", n), edge_prob=0.5,
            client_drop_prob=0.3)
        mask_fn = stoch.make_participation_sampler(n, jax.random.PRNGKey(13),
                                                   0.7)
        for t in range(4):
            keys = jax.random.split(jax.random.PRNGKey(t),
                                    K * n).reshape(K, n, 2)
            st = step(st, kb, keys, w_fn(jnp.int32(t)), mask_fn(jnp.int32(t)))
        outs[impl] = st
    _assert_state_close(outs["dense"], outs["fused_round"], 5e-6,
                        msg=f"{family}/{backend}/")


# ---------------------------------------------------------------------------
# error-feedback compression protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", QUANT_METHODS)
def test_ef_residual_identity_bitwise(method):
    """Q(v) + e == v exactly in f32 (Sterbenz for bf16 truncation; exact
    subtraction around the shared per-row scale for int8) — the property
    that makes error feedback lossless over time, not just approximately."""
    key = jax.random.PRNGKey(3)
    delta = jax.random.normal(key, (8, 257), jnp.float32) * \
        jnp.exp(jax.random.normal(jax.random.fold_in(key, 1), (8, 257)) * 3)
    ef = jax.random.normal(jax.random.fold_in(key, 2), (8, 257),
                           jnp.float32) * 0.1
    q, e_new = compression.ef_transmit(delta, ef, method)
    np.testing.assert_array_equal(np.asarray(q + e_new),
                                  np.asarray(delta + ef))
    assert wire_bits(method) in (8, 16)


@pytest.mark.parametrize("method", QUANT_METHODS)
def test_ef_transmit_masked_rows_hold_residual(method):
    """Inactive clients transmit Q(0) = 0 and their residual is untouched —
    churn must not leak or destroy banked compression error."""
    key = jax.random.PRNGKey(5)
    delta = jax.random.normal(key, (6, 64), jnp.float32)
    ef = jax.random.normal(jax.random.fold_in(key, 1), (6, 64), jnp.float32)
    mask = jnp.asarray([1, 0, 1, 0, 0, 1], jnp.float32)
    q, e_new = compression.ef_transmit(delta, ef, method, mask=mask)
    inactive = ~np.asarray(mask, bool)
    np.testing.assert_array_equal(np.asarray(q)[inactive], 0.0)
    np.testing.assert_array_equal(np.asarray(e_new)[inactive],
                                  np.asarray(ef)[inactive])


@pytest.mark.parametrize("impl,backend", [("pallas_packed", "xla"),
                                          ("fused_round", "xla"),
                                          ("fused_round", "interpret")])
@pytest.mark.parametrize("method", QUANT_METHODS)
def test_sum_c_zero_under_compressed_gossip(impl, backend, method):
    """The same transmitted q rides the correction AND the mixing, so
    Lemma 8's Σ_i c_i = 0 telescopes exactly through lossy quantization."""
    st = _run_rounds("kgt_minimax", impl, backend, rounds=5, compress=method)
    for c in (st.cx, st.cy):
        mean_c = jax.tree.leaves(jax.tree.map(lambda v: v.mean(0), c))[0]
        assert float(jnp.abs(mean_c).max()) < 1e-5, (impl, method)


def test_compressed_vs_exact_divergence_bounded():
    """100 int8-compressed rounds track the exact trajectory: EF keeps the
    quantization error from accumulating — divergence stays near the f32
    noise floor instead of growing with the round count."""
    exact = _run_rounds("kgt_minimax", "fused_round", "xla", rounds=100)
    comp = _run_rounds("kgt_minimax", "fused_round", "xla", rounds=100,
                       compress="int8")
    for name in ("x", "y"):
        a = jax.tree.leaves(getattr(exact, name))[0]
        b = jax.tree.leaves(getattr(comp, name))[0]
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-12))
        assert rel < 1e-3, (name, rel)


def test_checkpoint_roundtrips_ef_state_bitexact(tmp_path):
    """The EF residual is algorithm state: dropping it at restore would
    replay banked error into the next transmit.  Round-trip through the
    engine checkpoint must be bit-exact, and resuming must produce the
    exact same next state as never having checkpointed."""
    st, step, kb, (n, K) = _round_setup("kgt_minimax", "fused_round", "xla",
                                        compress="int8")
    for t in range(3):
        keys = jax.random.split(jax.random.PRNGKey(t), K * n).reshape(K, n, 2)
        st = step(st, kb, keys)
    assert st.ef_x is not None and st.ef_y is not None
    assert float(jnp.abs(st.ef_x).max()) > 0  # int8 actually banked error
    path = str(tmp_path / "ef_ckpt")
    ckpt_lib.save(path, st)
    st2 = ckpt_lib.restore(path, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    keys = jax.random.split(jax.random.PRNGKey(9), K * n).reshape(K, n, 2)
    out1, out2 = step(st, kb, keys), step(st2, kb, keys)
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_scan_carries_ef_state_bitexact():
    """The scan engine is pytree-generic: a chunked run of the fused round
    with int8 EF gossip must be bit-identical to the per-round host loop,
    EF residual leaves included — compression adds state, not special
    cases, to the engine."""
    from repro.engine import engine as engine_lib
    from repro.engine import sampler as sampler_lib

    st, step, kb, (n, K) = _round_setup("kgt_minimax", "fused_round", "xla",
                                        compress="int8")
    sampler = sampler_lib.make_fixed_batch_sampler(
        kb, local_steps=K, num_clients=n, seed=3)
    chunk = jax.jit(engine_lib.chunk_program(step, sampler, None, length=6),
                    donate_argnums=())
    scanned, _ = chunk(st, jnp.int32(5))
    host = st
    for t in range(6):
        batches, keys = sampler(host.round)
        host = step(host, batches, keys)
    assert scanned.ef_x is not None
    for a, b in zip(jax.tree.leaves(scanned), jax.tree.leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncompressed_state_has_no_ef_leaves():
    """gossip_compress=None must not change the state pytree: old
    checkpoints and the engine's donated-buffer layout stay valid."""
    st, _, _, _ = _round_setup("kgt_minimax", "pallas_packed", "xla")
    assert st.ef_x is None and st.ef_y is None


# ---------------------------------------------------------------------------
# configuration validation — loud rejections, no silent fallbacks
# ---------------------------------------------------------------------------

def test_compress_requires_packed_impl():
    with pytest.raises(ValueError, match="gossip_compress"):
        _round_setup("kgt_minimax", "dense", "auto", compress="int8")


def test_fused_round_requires_affine_coeffs():
    key = jax.random.PRNGKey(0)
    data = make_quadratic_data(key, 4, dx=6, dy=3)
    prob = dataclasses.replace(quadratic_problem(data), affine_coeffs=None)
    cfg = AlgorithmConfig(num_clients=4, local_steps=2, eta_cx=0.01,
                          eta_cy=0.05, mixing_impl="fused_round",
                          gossip_backend="xla")
    with pytest.raises(ValueError, match="affine"):
        make_round_step(prob, cfg)


def test_fused_round_rejects_byzantine():
    with pytest.raises(ValueError, match="byzantine|adversary"):
        _round_setup("kgt_minimax", "fused_round", "xla", byzantine=True)


def test_fused_round_has_no_standalone_mixer():
    with pytest.raises(ValueError, match="fused_round"):
        mixing.make_mixer("fused_round")


def test_validate_method():
    assert compression.validate_method(None) is None
    assert compression.validate_method("none") is None
    assert compression.validate_method("int8") == "int8"
    with pytest.raises(ValueError, match="int4"):
        compression.validate_method("int4")


def test_point_program_text_is_the_run_point_chunk():
    """The HLO a caller inspects for the lowering is the chunk run_point
    compiles: the XLA oracle backend holds no Pallas kernel call."""
    from repro.sweep import run as sweep_run

    p = dict(n=4, K=2, mixing_impl="fused_round", max_rounds=4,
             eval_every=2, gossip_backend="xla")
    txt = sweep_run.point_program_text(p, 2)
    assert "HloModule" in txt
    assert "tpu_custom_call" not in txt
