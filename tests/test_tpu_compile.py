"""The gossip kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so each test here lowers a kernel
at a real size for one chip of a ``v5e:2x2`` topology and compiles it:
what the chip's compiler refuses (VMEM overflow, unsupported loads) fails
here, at no chip time.  Nothing runs.  The topology is described inside a
module-scoped fixture — never while a module is imported — because only
one process may load the TPU library, and the worker that is given this
file keeps it until it exits.
"""
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
