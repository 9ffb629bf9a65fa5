"""Sparse neighbor-gather gossip-epilogue Pallas kernel.

The dense kernel in ``kernels/gossip.py`` contracts the full ``(n, n)``
mixing matrix against each ``(n, BD)`` state tile — O(n²·D) work and O(n²)
VMEM for W, which caps the clients axis.  On a sparse topology W has only
``deg_i`` non-zeros per row, so this kernel computes the same Algorithm-1
round epilogue

    WΔ    = Σ_slot w[:, slot] · Δ[idx[:, slot]]     (neighbor-row gather)
    Wθ    = Σ_slot w[:, slot] · θ[idx[:, slot]]
    θ_new = Wθ + η_s · WΔ
    c_new = c + s · (Δ − WΔ)                        (s = ±1/(K·η_c))

by gathering neighbor rows from the packed ``(n, BD)`` tile — O(n·m·D)
work with ``m = max_degree + 1`` slots.  The wrapper
(``ops.sparse_gossip_round``) prepends an *augmented self slot*
(idx = own row, weight = w_ii), so the kernel body is one uniform
gather-axpy loop with no special diagonal case; padding slots carry
weight 0.0 and contribute exact zeros.

Grid: (D tiles, client blocks).  Each program writes one ``(BN, BD)``
block of θ_new/c_new.  Its gather sources are the whole client axis of
the D tile — ``Δ[:, tile]`` and ``θ[:, tile]`` as ``(N, BD)`` VMEM blocks,
whose block index does not change along the inner client-block axis, so
they are fetched once per D tile.  Each output row loops over its
``m = max_degree + 1`` slots (unrolled at trace time — m is static and
small, ~2·log₂ n for the exponential graph), reading the neighbor index and
weight as SMEM scalars and the neighbor's row as a dynamic one-row slice of
the source block.  ``ops.sparse_gossip_round`` sizes BD so the sources fit
the scoped VMEM budget and raises where even one lane tile cannot.

``gossip_dtype`` narrows the *operands* (weights and gathered values) and
accumulates in f32 — matching the MXU's exact-product bf16×bf16→f32
semantics of the dense kernel, so sparse and dense agree to accumulation
order.  The wrapper narrows the weights before they reach SMEM (scalar
memory holds 32-bit words).  Scalars (η_s, s) ride in via scalar prefetch:
they are traced (lr schedule).  Callers go through
``ops.sparse_gossip_round``, which pads n to the client-block multiple and D
to the block multiple and slices back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, nidx_ref, nw_ref, delta_ref, theta_ref, c_ref,
            theta_out_ref, c_out_ref, *, block_n, gossip_dtype):
    eta_s = s_ref[0]
    corr_scale = s_ref[1]
    m = nidx_ref.shape[1]
    row0 = pl.program_id(1) * block_n

    def narrow(x):
        x = x.astype(jnp.float32)
        if gossip_dtype is None:
            return x
        return x.astype(gossip_dtype).astype(jnp.float32)

    def row(r, carry):
        wd = jnp.zeros((1, delta_ref.shape[1]), jnp.float32)
        wt = jnp.zeros_like(wd)
        for slot in range(m):                       # static unroll
            j = nidx_ref[r, slot]                   # SMEM scalars
            w = nw_ref[r, slot]
            wd = wd + w * narrow(delta_ref[pl.ds(j, 1), :])
            wt = wt + w * narrow(theta_ref[pl.ds(j, 1), :])
        own = delta_ref[pl.ds(row0 + r, 1), :].astype(jnp.float32)
        theta_out_ref[pl.ds(r, 1), :] = (wt + eta_s * wd).astype(
            theta_out_ref.dtype)
        c_out_ref[pl.ds(r, 1), :] = (
            c_ref[pl.ds(r, 1), :].astype(jnp.float32)
            + corr_scale * (own - wd)).astype(c_out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_n, row, 0)


def sparse_gossip_nd(neighbor_idx, neighbor_w, delta, theta, c, scalars, *,
                     block_d: int, block_n: int, gossip_dtype=None,
                     interpret: bool):
    """neighbor_idx/neighbor_w: (N, M) *augmented* slots (slot 0 = self),
    the weights already narrowed to ``gossip_dtype`` and held as f32;
    delta/theta/c: (N, D) with N a ``block_n`` multiple and D a ``block_d``
    multiple (padding handled by ``ops.sparse_gossip_round``); scalars: (2,)
    f32 = [η_s, corr_scale].  Returns (θ_new, c_new) f32."""
    n, d = delta.shape
    m = neighbor_idx.shape[1]
    assert neighbor_idx.shape == (n, m) and neighbor_w.shape == (n, m)
    assert theta.shape == c.shape == (n, d)
    assert d % block_d == 0 and n % block_n == 0, (n, d, block_n, block_d)

    kernel = functools.partial(_kernel, block_n=block_n,
                               gossip_dtype=gossip_dtype)
    # index maps receive (grid indices, *scalar prefetch refs)
    rows = lambda j, i, *_: (i, 0)
    source = lambda j, i, *_: (0, j)                 # whole client axis
    tile = lambda j, i, *_: (i, j)
    smem = dict(memory_space=pltpu.SMEM)
    out_sds = jax.ShapeDtypeStruct((n, d), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                   # [η_s, corr_scale]
            grid=(d // block_d, n // block_n),
            in_specs=[
                pl.BlockSpec((block_n, m), rows, **smem),   # neighbor ids
                pl.BlockSpec((block_n, m), rows, **smem),   # weights
                pl.BlockSpec((n, block_d), source),         # Δ (gathered)
                pl.BlockSpec((n, block_d), source),         # θ (gathered)
                pl.BlockSpec((block_n, block_d), tile),     # c
            ],
            out_specs=[
                pl.BlockSpec((block_n, block_d), tile),     # θ_new
                pl.BlockSpec((block_n, block_d), tile),     # c_new
            ],
        ),
        out_shape=[out_sds, out_sds],
        interpret=interpret,
    )(scalars, neighbor_idx, neighbor_w, delta, theta, c)
