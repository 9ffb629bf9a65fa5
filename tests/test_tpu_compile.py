"""The gossip kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so each test here lowers a kernel
at a real size for one chip of a ``v5e:2x2`` topology and compiles it:
what the chip's compiler refuses (VMEM overflow, unsupported loads) fails
here, at no chip time.  Nothing runs.  The topology is described inside a
module-scoped fixture — never while a module is imported — because only
one process may load the TPU library, and the worker that is given this
file keeps it until it exits.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sparse_topology as sparse_lib
from repro.kernels import ops

K_STEPS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(*shape, dtype=f32)``: an abstract argument on one chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_round_args(sds, n, dz):
    return (sds(n, n), sds(n, dz), sds(n, dz), sds(n, dz),
            sds(n, dz, dz), sds(K_STEPS, n, dz), sds(n, dz), sds(n, dz),
            sds(n, dz), sds(n, dz))


def _largest_admitted_dz(n: int) -> int:
    dz = 128
    while ops.fused_round_vmem_bytes(n, dz + 128, K_STEPS) \
            <= ops.SCOPED_VMEM_BYTES:
        dz += 128
    return dz


def test_fused_gossip_compiles_at_4_clients_4m_columns(sds):
    n, d = 4, 2 ** 22
    txt = _compiled_text(
        lambda w, dl, th, c, e, s: ops.fused_gossip_round(
            w, dl, th, c, e, s, backend="pallas"),
        sds(n, n), sds(n, d), sds(n, d), sds(n, d), sds(), sds())
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dz", [256, "largest"])
def test_fused_round_compiles(sds, dz):
    n = 8
    if dz == "largest":
        dz = _largest_admitted_dz(n)
    txt = _compiled_text(
        lambda *a: ops.fused_round(*a, backend="pallas"),
        *_fused_round_args(sds, n, dz))
    assert "tpu_custom_call" in txt


def test_fused_round_guard_is_the_compiler_bound():
    """One 128-lane step past the largest admitted dz the wrapper raises —
    where the v5e compiler reports a scoped-VMEM overflow (17.28 MiB at
    n=8, K=8, dz=640)."""
    n = 8
    dz = _largest_admitted_dz(n)
    assert dz == 512
    z = jnp.zeros((n, dz + 128), jnp.float32)
    with pytest.raises(ValueError, match="scoped limit"):
        ops.fused_round(jnp.eye(n), z, z, z,
                        jnp.zeros((n, dz + 128, dz + 128)),
                        jnp.zeros((K_STEPS, n, dz + 128)), z, z, z, z,
                        backend="pallas")


@pytest.mark.parametrize("n", [64, 4096])
def test_sparse_gossip_compiles_on_exp_graph(sds, n):
    d = 128
    topo_w = sparse_lib.sparse_mixing_matrix("exp", n)
    m = topo_w.neighbor_idx.shape[1]
    txt = _compiled_text(
        lambda i, w, sw, dl, th, c, e, s: ops.sparse_gossip_round(
            i, w, sw, dl, th, c, e, s, backend="pallas"),
        sds(n, m, dtype=jnp.int32), sds(n, m), sds(n),
        sds(n, d), sds(n, d), sds(n, d), sds(), sds())
    assert "tpu_custom_call" in txt


def test_fused_round_past_the_guard_overflows_vmem(sds):
    """The other side of the bound: the kernel itself, one step past what
    the guard admits, is refused by the v5e compiler."""
    from repro.kernels import fused_round as fround_lib

    n, dz = 8, _largest_admitted_dz(8) + 128
    with pytest.raises(Exception, match="vmem"):
        _compiled_text(
            lambda *a: fround_lib.fused_round_nd(*a, k_steps=K_STEPS,
                                                 interpret=False),
            *_fused_round_args(sds, n, dz))


#: sha256 of paper-toy's compiled round (smoke-test width, n=4, K=2, B=2,
#: S=32) for one v5e chip, without its source locations (the stack-frame
#: tables and each instruction's metadata), as the tree before Granite's
#: multipliers compiled it.
PAPER_TOY_ROUND_SHA256 = (
    "3191dc63ea3564af43e115c726310871af3edfb3044750b6b60500e2698b2882")
_FRAMES = re.compile(r"\nFileNames\n.*?\nStackFrames\n(?:\d+ [^\n]*\n)*",
                     re.S)


def _paper_toy_round_text(sds) -> str:
    from repro.configs import registry
    from repro.configs.base import AlgorithmConfig
    from repro.core import kgt_minimax as kgt
    from repro.core import objectives

    cfg = registry.reduced(registry.get_model_config("paper-toy"))
    algo = AlgorithmConfig(num_clients=4, local_steps=2, eta_cx=0.01,
                           eta_cy=0.5, eta_sx=0.7, eta_sy=0.7)
    problem = objectives.dro_problem(cfg, num_groups=8)
    step = kgt.make_round_step(problem, algo)
    x1 = jax.eval_shape(problem.init_x, jax.random.PRNGKey(0))
    rep = lambda t: jax.tree.map(lambda s: sds(4, *s.shape, dtype=s.dtype), t)
    state = kgt.KGTState(x=rep(x1), y=sds(4, 8), cx=rep(x1), cy=sds(4, 8),
                         round=sds(dtype=jnp.int32))
    tok = sds(2, 4, 2, 32, dtype=jnp.int32)
    batch = {"tokens": tok, "labels": tok, "groups": tok}
    text = jax.jit(step).lower(state, batch,
                               sds(2, 4, 2, dtype=jnp.uint32)).compile(
                                   ).as_text()
    return re.sub(r",? metadata=\{[^{}]*\}", "", _FRAMES.sub("\n", text))


def test_paper_toy_round_is_unchanged_by_the_multipliers(sds):
    """Granite's multipliers, the attention scale and the MoE counters
    default to emitting no operation: paper-toy's optimized round is the
    one it was, instruction for instruction."""
    text = _paper_toy_round_text(sds)
    assert hashlib.sha256(text.encode()).hexdigest() == PAPER_TOY_ROUND_SHA256


def test_moe_share_grouped_products_compile_under_vmap(sds):
    """The share's expert layer at its published widths, forward and
    backward, vmapped over 4 clients: XLA's TPU ragged-dot takes no batch
    dimension, so each client's grouped products are their own kernels."""
    from repro.configs import registry
    from repro.models import moe as moe_lib

    cfg = registry.get_model_config("granite-moe-1b-a400m-ep4")
    m, d, n, t = cfg.moe, cfg.d_model, 4, 1024
    params = {"router": sds(n, d, m.num_experts),
              "gate": sds(n, m.held, d, m.expert_d_ff),
              "up": sds(n, m.held, d, m.expert_d_ff),
              "down": sds(n, m.held, m.expert_d_ff, d)}

    def loss(p, x):
        out, aux, _ = moe_lib.moe_mlp_sorted(p, x, cfg)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    txt = _compiled_text(jax.vmap(jax.grad(loss, argnums=(0, 1))), params,
                         sds(n, 1, t, d, dtype=jnp.bfloat16))
    # forward, input and weight gradients of 3 products, for 4 clients
    assert len(re.findall(r"^\s*%ragged-dot-(?!metadata)[\w.-]+ = ", txt,
                          re.M)) == 3 * 3 * n


def test_sorted_moe_is_refused_on_a_clients_sharded_tpu_mesh(topo):
    """Over 4 chips that split the clients axis, one slice per client would
    move operands between chips and the batched ragged-dot does not compile
    for the TPU: the round builder says so before any tracing."""
    import numpy as np

    from repro.configs import registry
    from repro.configs.base import InputShape, MeshConfig
    from repro.dist import compat
    from repro.dist import sharding as sh
    from repro.launch import steps

    cfg = registry.reduced(registry.get_model_config(
        "granite-moe-1b-a400m-ep4"))
    mesh = compat.mesh_of(np.array(topo.devices).reshape(4, 1, 1),
                          (sh.CLIENTS, sh.FSDP, sh.MODEL))
    with pytest.raises(ValueError, match="--mesh host"):
        steps.build_train_round(
            cfg, InputShape(name="t", seq_len=32, global_batch=8,
                            kind="train"),
            mesh, MeshConfig(num_clients=4, fsdp=1, model=1,
                             param_mode="replicated"))
