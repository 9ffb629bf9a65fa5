"""The K-GT-Minimax round with its clients axis split across devices
(``make_round_step(..., clients_sharded=True)``): the same numbers as the
round on one device, and the structure of its gossip."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PARITY = """
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.configs.base import AlgorithmConfig
from repro.core import kgt_minimax as kgt, objectives
from repro.launch import mesh as mesh_lib

cfg = registry.reduced(registry.get_model_config("paper-toy"))
n, K, B, S = 4, 2, 2, 32
algo = AlgorithmConfig(num_clients=n, local_steps=K, eta_cx=0.01, eta_cy=0.5,
                       eta_sx=0.7, eta_sy=0.7, topology="ring",
                       mixing_impl="dense")
# f32 compute: a bf16 forward would turn f32 rounding differences into
# bf16 rounding flips of the weights from the second round on
problem = objectives.dro_problem(cfg, num_groups=8,
                                 compute_dtype=jnp.float32)
rng = np.random.default_rng(0)

def batch():
    tok = rng.integers(0, cfg.vocab_size, (K, n, B, S)).astype(np.int32)
    return {"tokens": tok, "labels": np.roll(tok, -1, -1),
            "groups": rng.integers(0, 8, (K, n, B, S)).astype(np.int32)}

init_b = jax.tree.map(lambda a: a[0], batch())
state = jax.device_get(kgt.init_state(problem, algo, jax.random.PRNGKey(0),
                                      init_batch=init_b))
rounds = [(batch(), np.asarray(jax.random.split(jax.random.PRNGKey(t),
                                                K * n)).reshape(K, n, 2))
          for t in range(3)]
mesh = mesh_lib.local_mesh(n)   # the trainer's mesh: clients over devices
state_sh = jax.tree.map(lambda a: NamedSharding(mesh, P("clients") if a.ndim
                                                else P()), state)
step_sh = NamedSharding(mesh, P(None, "clients"))
outs = {}
for sharded in (False, True):
    step = kgt.make_round_step(problem, algo, clients_sharded=sharded)
    if sharded:
        step = jax.jit(step, in_shardings=(state_sh, step_sh, step_sh),
                       out_shardings=state_sh)
        put = lambda t: jax.device_put(t, step_sh)
        s = jax.device_put(state, state_sh)
    else:
        step = jax.jit(step)
        put = lambda t: jax.device_put(t, jax.devices()[0])
        s = put(state)
    for b, k in rounds:
        s = step(s, put(b), put(k))
    if sharded:
        assert all(len(l.sharding.device_set) == n
                   for l in jax.tree.leaves(s.x))
    outs[sharded] = jax.device_get(s)
one, split = outs[False], outs[True]
gaps = {name: max(float(np.max(np.abs(p - q)) / np.max(np.abs(p)))
                  for p, q in zip(jax.tree.leaves(getattr(one, name)),
                                  jax.tree.leaves(getattr(split, name))))
        for name in ("x", "y", "cx", "cy")}
print(json.dumps({"gaps": gaps, "round": [int(one.round), int(split.round)]}))
"""


def _run(script: str) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_round_matches_one_device():
    """Three rounds of reduced paper-toy with the clients over 4 devices
    against the same rounds on one device: the gossip's contraction and
    the fusions around it differ, so the states agree to f32 rounding.  The
    corrections amplify it by 1/(K·η_c) = 50 over Δ − WΔ, a difference of
    nearly equal terms, hence their looser bound."""
    got = _run(_PARITY)
    assert got["round"] == [3, 3]
    gaps = got["gaps"]
    assert gaps["x"] < 1e-5 and gaps["y"] < 1e-5, gaps
    assert gaps["cx"] < 2e-3 and gaps["cy"] < 2e-3, gaps


def _round_jaxpr(clients_sharded: bool):
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.configs.base import AlgorithmConfig
    from repro.core import kgt_minimax as kgt
    from repro.core import objectives

    cfg = registry.reduced(registry.get_model_config("paper-toy"))
    n, k = 4, 2
    algo = AlgorithmConfig(num_clients=n, local_steps=k, topology="ring",
                           mixing_impl="dense")
    problem = objectives.dro_problem(cfg, num_groups=8)
    state = jax.eval_shape(
        lambda key: kgt.init_state(problem, algo, key),
        jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((k, n, 2, 32), jnp.int32)
    batch = {"tokens": tok, "labels": tok, "groups": tok}
    keys = jax.ShapeDtypeStruct((k, n, 2), jnp.uint32)
    step = kgt.make_round_step(problem, algo, clients_sharded=clients_sharded)
    closed = jax.make_jaxpr(step)(state, batch, keys)
    n_x = len(jax.tree.leaves(state.x))
    return closed.jaxpr, closed.jaxpr.invars[:n_x], k


@pytest.mark.parametrize("clients_sharded", [False, True])
def test_parameter_gossip_reads_the_rounds_starting_state(clients_sharded):
    """Split across devices, the round gossips every leaf of x from its
    starting state before its local steps, which wait for that gossip (an
    optimization barrier over Wx, Wy and the steps' starting point) and run
    unrolled; on one device none of the three."""
    jaxpr, x_vars, k = _round_jaxpr(clients_sharded)
    scans = [i for i, e in enumerate(jaxpr.eqns)
             if e.primitive.name == "scan"]
    assert len(scans) == 1
    scan = jaxpr.eqns[scans[0]]
    assert scan.params["unroll"] == (k if clients_sharded else 1)
    early = jaxpr.eqns[:scans[0]]
    barriers = [e for e in early if e.primitive.name == "optimization_barrier"]
    gossip_in = {v for e in early if e.primitive.name != "optimization_barrier"
                 for v in e.invars}
    read_early = [v in gossip_in for v in x_vars]
    if not clients_sharded:
        assert not barriers and not any(read_early)
        return
    assert all(read_early) and len(barriers) == 1
    barrier = barriers[0]
    assert set(x_vars) <= set(barrier.invars)
    # the barrier also holds the gossip's results, and the scan starts from
    # the barrier's copy of x
    assert len(barrier.invars) > 2 * len(x_vars)
    n_consts = scan.params["num_consts"]
    x_init = scan.invars[n_consts:n_consts + len(x_vars)]
    assert set(x_init) <= set(barrier.outvars)
