"""One chip's share of a sparse-expert layer (``MoEConfig.experts_held``):
the shares add up to the uncut layer, no routed row is lost, and the share
of ``granite-moe-1b-a400m-ep4`` trains as the plain reference
(``bench/reference/dro_moe.py``) does, at a small size on the CPU."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AlgorithmConfig, ModelConfig, MoEConfig
from repro.core import kgt_minimax as kgt
from repro.core import objectives
from repro.models import moe as moe_lib
from repro.models import model as model_lib

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")

E, HELD, K, D, F = 8, 2, 4, 32, 16


def _cfg(held: int) -> ModelConfig:
    return ModelConfig(name="moe-share", arch_type="moe", num_layers=1,
                       d_model=D, num_heads=2, num_kv_heads=1, d_ff=F,
                       vocab_size=64, moe=MoEConfig(
                           num_experts=E, top_k=K, expert_d_ff=F,
                           dispatch="sorted", experts_held=held))


def _share(params, s: int):
    """Shard ``s``'s params: experts [s·HELD, (s+1)·HELD), with the router's
    columns turned so that they are its first ones."""
    lo = s * HELD
    out = {k: params[k][lo:lo + HELD] for k in ("gate", "up", "down")}
    out["router"] = jnp.roll(params["router"], -lo, axis=1)
    return out


@pytest.fixture(scope="module")
def layer():
    params = moe_lib.init_moe(jax.random.PRNGKey(0), _cfg(0), D)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8, D))
    return params, x


@pytest.mark.parametrize("batched", [False, True])
def test_the_shares_add_up_to_the_uncut_layer(layer, batched):
    params, x = layer
    full = lambda xx: moe_lib.moe_mlp_sorted(params, xx, _cfg(0),
                                             jnp.float32)
    share = lambda s, xx: moe_lib.moe_mlp_sorted(
        _share(params, s), xx, _cfg(HELD), jnp.float32)
    if batched:   # under vmap: one grouped product per client
        full, share = jax.vmap(full), jax.vmap(share, in_axes=(None, 0))
    else:
        x = x[0]
    want, aux, _ = full(x)
    parts = [share(s, x) for s in range(E // HELD)]
    np.testing.assert_allclose(sum(p[0] for p in parts), want, atol=1e-5)
    for _, a, _ in parts:   # every share routes over all experts alike
        np.testing.assert_allclose(a, aux, rtol=1e-6)


def test_the_shares_gradients_add_up(layer):
    """Against a fixed linear read-out of the layer's output: the input's
    gradient is the sum of the shares', each share's experts get the uncut
    layer's gradient of those experts, and the router's adds up."""
    params, x = layer
    x = x[0]
    c = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    read = lambda cfg: lambda p, xx: jnp.sum(
        c * moe_lib.moe_mlp_sorted(p, xx, cfg, jnp.float32)[0])
    gp, gx = jax.grad(read(_cfg(0)), argnums=(0, 1))(params, x)
    sum_x = jnp.zeros_like(x)
    sum_router = jnp.zeros_like(params["router"])
    for s in range(E // HELD):
        lo = s * HELD
        sp, sx = jax.grad(read(_cfg(HELD)), argnums=(0, 1))(
            _share(params, s), x)
        sum_x = sum_x + sx
        sum_router = sum_router + jnp.roll(sp["router"], lo, axis=1)
        for k in ("gate", "up", "down"):
            np.testing.assert_allclose(sp[k], gp[k][lo:lo + HELD],
                                       atol=1e-5)
    np.testing.assert_allclose(sum_x, gx, atol=1e-5)
    np.testing.assert_allclose(sum_router, gp["router"], atol=1e-5)


def test_dropless_when_every_token_picks_one_held_expert():
    """Router logits that put held expert 0 first for every token: each of
    its rows is computed, and the counters see them all."""
    cfg = _cfg(HELD)
    params = moe_lib.init_moe(jax.random.PRNGKey(2), cfg, D)
    params["router"] = params["router"].at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 16, D))) + 0.5
    out, _, stats = moe_lib.moe_mlp_sorted(params, x, cfg, jnp.float32)
    xt = x.reshape(-1, D)
    idx, gates, _ = moe_lib.route(params["router"], xt, cfg.moe)
    assert bool((idx[:, 0] == 0).all())
    # plain: every held expert on every token, weighted by its gate
    want = jnp.zeros_like(xt)
    for h in range(HELD):
        y = (jax.nn.silu(xt @ params["gate"][h]) * (xt @ params["up"][h])
             ) @ params["down"][h]
        weight = jnp.sum(jnp.where(idx == h, gates, 0.0), axis=1)
        want = want + weight[:, None] * y
    np.testing.assert_allclose(out.reshape(-1, D), want, atol=1e-5)
    assert int(stats["rows"][0]) == xt.shape[0]
    assert int(stats["rows"].sum()) == int(stats["held"].sum())


# -- the share of granite-moe-1b-a400m against the plain reference ----------

SMALL = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab_size": 512, "num_experts": 4,
         "experts_held": 1, "top_k": 2, "expert_d_ff": 64}


@pytest.fixture(scope="module")
def reference():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import json

    from reference import dro_moe
    from reference.topology import mixing_matrix

    with open(os.path.join(BENCH, "configs",
                           "granite-moe-1b-a400m-ep4.json")) as f:
        cfg = dict(json.load(f), **SMALL)
    job = {"clients": 2, "local_steps": 2, "batch": 2, "seq_len": 16,
           "groups": 4, "mu": 1.0, "alpha": 0.3,
           "eta": {"cx": 0.01, "cy": 0.5, "s": 0.7},
           "mixing_matrix": mixing_matrix("ring", 2)}
    return dro_moe, dro_moe.Job(cfg, job, dro_moe.Numerics())


def test_the_reduced_share_keeps_its_multipliers_and_share():
    cfg = registry.reduced(registry.get_model_config(
        "granite-moe-1b-a400m-ep4"))
    assert {k: getattr(cfg, k) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads",
        "resolved_head_dim", "vocab_size")} == {
        k: SMALL[k if k != "resolved_head_dim" else "head_dim"]
        for k in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "resolved_head_dim", "vocab_size")}
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k,
            cfg.moe.expert_d_ff, cfg.moe.dispatch) == (4, 1, 2, 64, "sorted")
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        12.0, 0.015625, 0.22, 6.0)


def test_system_against_reference_one_chunk(reference):
    """Four rounds of Algorithm 1 on the reduced share, the program in
    float32 against the reference at HIGHEST, from the same seed: the same
    weights, the same data, the same trajectory."""
    dro_moe, job = reference
    cfg = registry.reduced(registry.get_model_config(
        "granite-moe-1b-a400m-ep4"))
    seed, rounds = 5, 4
    (x_r, y_r, cx_r, cy_r), dm, kt, _ = job.init(seed)
    algo = AlgorithmConfig(num_clients=2, local_steps=2, eta_cx=0.01,
                           eta_cy=0.5, eta_sx=0.7, eta_sy=0.7)
    problem = objectives.dro_problem(cfg, num_groups=4,
                                     compute_dtype=jnp.float32)
    _, ki, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    x0_ref, _, _, _, init_b = job.draws(seed)
    state = kgt.init_state(problem, algo, ki, init_batch=init_b)
    name = lambda t: {".".join(str(k.key) for k in p
                               if isinstance(k, jax.tree_util.DictKey)
                               and k.key != "stack"): v
                      for p, v in jax.tree_util.tree_leaves_with_path(t)}
    for k, v in name(state.x).items():   # the same initial weights
        np.testing.assert_array_equal(v[0], x0_ref[k])
    step = jax.jit(kgt.make_round_step(problem, algo))
    ref_state = (x_r, y_r, cx_r, cy_r)
    for r in range(rounds):
        batches = job._batches(dm, jax.random.fold_in(kt, r))
        keys = jnp.zeros((2, 2, 2), jnp.uint32)
        state = step(state, batches, keys)
        ref_state = job._round(ref_state, batches)
    gaps = {}
    for part, got, want in (("x", state.x, ref_state[0]),
                            ("cx", state.cx, ref_state[2])):
        got = name(got)
        for k, w in want.items():
            gaps[f"{part}.{k}"] = float(jnp.linalg.norm(got[k] - w)
                                        / jnp.linalg.norm(w))
    gaps["y"] = float(jnp.linalg.norm(state.y - ref_state[1])
                      / jnp.linalg.norm(ref_state[1]))
    assert max(gaps.values()) < 1e-4, gaps
    assert float(state.counters["moe_rows_held"]) > 0


def test_program_routes_as_the_reference_on_the_first_step(reference):
    dro_moe, job = reference
    cfg = registry.reduced(registry.get_model_config(
        "granite-moe-1b-a400m-ep4"))
    x0, batches, ref_held = job.first_step_held(3)
    sys.path.insert(0, BENCH)
    import run as bench_run

    entry = bench_run.load_module(
        os.path.join(BENCH, "entries", "train_moe.py"), "bench_entry_moe")
    got = jax.vmap(lambda b: model_lib.per_group_loss(
        entry.program_params(x0), b, cfg, num_groups=4,
        compute_dtype=jnp.float32)[2]["held"])(batches)
    assert got.shape == ref_held.shape
    assert np.mean(np.asarray(got) != ref_held) < 0.01


def test_param_counts_of_the_share():
    cfg = registry.get_model_config("granite-moe-1b-a400m-ep4")
    assert cfg.param_count() == 119_817_216
    x = jax.eval_shape(lambda k: model_lib.init_params(cfg, k),
                       jax.random.PRNGKey(0))
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(x)) \
        == cfg.param_count()
    # 2 of the 8 held experts' weights are expected in use per token
    expert = 3 * 1024 * 512
    assert cfg.active_param_count() == cfg.param_count() - 8 * 6 * expert
    full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=0))
    assert full.param_count() - cfg.param_count() == 8 * 24 * expert


_SHARDED_ROUND = """
import os, re, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs import registry
from repro.configs.base import AlgorithmConfig, InputShape, MeshConfig
from repro.dist import compat, sharding as sh
from repro.launch import steps
cfg = registry.reduced(registry.get_model_config("granite-moe-1b-a400m-ep4"))
# a W that weighs every client: its gossip is all-gathers, so that any
# collective permute would come from the expert layer
algo = AlgorithmConfig(num_clients=4, topology="full")
mesh = compat.mesh_of(np.array(jax.devices()).reshape(4, 1, 1),
                      (sh.CLIENTS, sh.FSDP, sh.MODEL))
mcfg = MeshConfig(num_clients=4, fsdp=1, model=1, param_mode="replicated")
shape = InputShape(name="t", seq_len=32, global_batch=8, kind="train")
with compat.use_mesh(mesh):
    jitted, state, batch, keys, _ = steps.build_train_round(cfg, shape, mesh,
                                                            mcfg, algo=algo)
    txt = jitted.lower(state, batch, keys).compile().as_text()
print(json.dumps({
    "counters": sorted(state.counters),
    "ops": {op: len(re.findall(r"= \\S+ " + op + r"(?:-start)?\\(", txt))
            for op in ("all-gather", "collective-permute", "all-reduce")}}))
"""


def test_sharded_clients_round_moves_no_client_and_keeps_the_counters():
    """On a 4-device clients axis the grouped products stay batched: one
    per-client slice each would add collective permutes (42 in this round).
    The gossip's all-gathers remain (the complete graph's W, which the
    sharded gossip contracts), and one all-reduce of the counters."""
    import json
    import subprocess

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(BENCH), "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _SHARDED_ROUND], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["counters"] == sorted(objectives.MOE_COUNTERS)
    assert got["ops"]["collective-permute"] == 0
    assert got["ops"]["all-gather"] > 0
    assert got["ops"]["all-reduce"] == 1
