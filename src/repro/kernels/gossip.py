"""Fused gossip-epilogue Pallas kernel (TPU target).

One kernel pass over the packed ``(n, D)`` client state computes the whole
round epilogue of Algorithm 1 (lines 7–11) for one variable:

    WΔ    = W @ Δ                      (the Δ-gossip, lines 7–8)
    Wθ    = W @ θ                      (the parameter gossip, lines 10–11)
    θ_new = Wθ + η_s · WΔ              (parameter mixing epilogue)
    c_new = c + s · (Δ − WΔ)           (tracking correction; s = ±1/(K·η_c))

Tiling: the grid is one program per D-tile; each program loads the full
``(n, n)`` mixing matrix W (n is the client count — tiny next to D) and an
``(n, BD)`` tile of Δ/θ/c, runs both matmuls on the MXU with f32
accumulation, and applies the epilogue in-register before the single write
back of θ_new/c_new.  The per-leaf lowering reads and writes every state
leaf 4+ times; this kernel reads Δ, θ, c once and writes θ_new, c_new once.

``gossip_dtype`` narrows only the matmul *operands* (what a multi-chip run
puts on the wire); Δ stays f32 inside the correction so the semantics match
``mixing.mix_dense`` + ``kgt_minimax._tree_axpy`` exactly.

Scalars (η_s, s) ride in via scalar prefetch — they are traced values
(η_c carries the lr schedule), so they cannot be baked into the kernel.

Callers go through ``repro.kernels.ops.fused_gossip_round``, which pads n
to the f32 sublane multiple and D to the lane/block multiple (ragged-D) and
slices the result back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, w_ref, delta_ref, theta_ref, c_ref, theta_out_ref,
            c_out_ref, *, gossip_dtype):
    eta_s = s_ref[0]
    corr_scale = s_ref[1]
    w = w_ref[...].astype(jnp.float32)              # (N, N)
    d32 = delta_ref[...].astype(jnp.float32)        # (N, BD)
    if gossip_dtype is None:
        wg, dg, tg = w, d32, theta_ref[...].astype(jnp.float32)
    else:
        wg = w.astype(gossip_dtype)
        dg = delta_ref[...].astype(gossip_dtype)
        tg = theta_ref[...].astype(gossip_dtype)
    dims = (((1,), (0,)), ((), ()))
    # f32 operands contract at f32 precision (not the MXU's one bf16 pass)
    prec = jax.lax.Precision.HIGHEST if gossip_dtype is None else None
    wd = jax.lax.dot_general(wg, dg, dims, precision=prec,
                             preferred_element_type=jnp.float32)
    wt = jax.lax.dot_general(wg, tg, dims, precision=prec,
                             preferred_element_type=jnp.float32)
    theta_out_ref[...] = (wt + eta_s * wd).astype(theta_out_ref.dtype)
    c_out_ref[...] = (c_ref[...].astype(jnp.float32)
                      + corr_scale * (d32 - wd)).astype(c_out_ref.dtype)


def fused_gossip_nd(w, delta, theta, c, scalars, *, block_d: int = 512,
                    gossip_dtype=None, interpret: bool):
    """w: (N, N); delta/theta/c: (N, D) with N a sublane multiple and D a
    ``block_d`` multiple (padding handled by ``ops.fused_gossip_round``);
    scalars: (2,) f32 = [η_s, corr_scale].  Returns (θ_new, c_new) f32."""
    n, d = delta.shape
    assert w.shape == (n, n) and theta.shape == c.shape == (n, d)
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)

    kernel = functools.partial(_kernel, gossip_dtype=gossip_dtype)
    # index maps receive (grid indices, *scalar prefetch refs)
    tile = lambda i, *_: (0, i)
    out_sds = jax.ShapeDtypeStruct((n, d), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // block_d,),
            in_specs=[
                pl.BlockSpec((n, n), lambda i, *_: (0, 0)),  # W: every tile
                pl.BlockSpec((n, block_d), tile),            # Δ
                pl.BlockSpec((n, block_d), tile),            # θ
                pl.BlockSpec((n, block_d), tile),            # c
            ],
            out_specs=[
                pl.BlockSpec((n, block_d), tile),            # θ_new
                pl.BlockSpec((n, block_d), tile),            # c_new
            ],
        ),
        out_shape=[out_sds, out_sds],
        interpret=interpret,
    )(scalars, w, delta, theta, c)
