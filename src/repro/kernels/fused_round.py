"""Whole-round fused Pallas kernel (TPU target).

``kernels/gossip.py`` fuses only the round *epilogue*; every one of the K
local SGDA steps still round-trips the client state through HBM, which is
why the epilogue-only lowering loses wall clock to plain dense XLA (the
pack/concat traffic outweighs the collective savings — see
results/benchmarks.json "gossip").  For the quadratic workload the local
step is **affine** in the packed state z = (x; y):

    (∇x f_i, ∇y f_i) = split(G_i z + h)        (MinimaxProblem.affine_coeffs)

so all K steps are K fused-multiply-adds against coefficients that fit in
VMEM — one kernel pass runs the entire Algorithm-1 round:

    repeat K:   z ← z − s ⊙ (G z + h_k + c)     (local SGDA; s = ±η_c ⊙ mask)
    Δ  = z_K − z₀
    q  = Δ                        (exact)    — or, compressed:
    v  = mask ⊙ (Δ + e);  q = Q(v);  e' = mask ? v − q : e
    z' = W z₀ + η_s ⊙ (W q)                    (parameter gossip + mixing)
    c' = c + corr ⊙ (q − W q)                  (tracking correction)

Per-column vectors ``s``/``η_s``/``corr`` carry the x/y split (opposite
descent/ascent signs, separate learning rates) and arrive as full
``(n, dz)`` f32 arrays — they are *traced* (lr schedules, churn masks), so
they ride in as operands rather than baked constants, and broadcasting them
host-side avoids scalar prefetch entirely.  ``corr = 0`` encodes the
no-tracking variants (c' = c exactly).  The correction is constant across
the K local steps (Algorithm 1 updates it only at the round boundary).

Compression uses the *same* ``kernels.quantize.quantize_dequant`` the
oracle and the core EF protocol import — three lowerings, one rounding
rule.  The transmitted q replaces Δ in both the mixing and the correction,
which is what preserves the Σc = 0 telescoping under any doubly stochastic
W (see ``core.compression``).

Memory: this kernel is grid-less — n is tiny (≤ a few hundred after the
sparse path takes over) and the G z contraction binds the full dz axis, so
every operand is a single VMEM block, inside Mosaic's default 16 MiB of
scoped VMEM on TPU v5e.  G (n·dz²·4 bytes) is the big one, and the batched
matvec's lane-padded right-hand side adds ~1 KiB per (client, dz) row:
``ops.fused_round_vmem_bytes`` counts it all, and ``ops.fused_round``
raises past the limit.  At n=8 and K=8 that admits dz ≤ 512 (G = 8 MiB);
at dz = 640 the v5e compiler reports 17.3 MiB and refuses.

``gossip_dtype`` narrows only the W-matmul operands (the wire values), as
in ``kernels/gossip.py``; Δ/q stay f32 inside the correction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import quantize_dequant


def _kernel(w_ref, z0_ref, c_ref, ef_ref, g_ref, h_ref, step_ref, etas_ref,
            corr_ref, mask_ref, z_out_ref, c_out_ref, e_out_ref, *,
            k_steps, compress, gossip_dtype):
    z0 = z0_ref[...].astype(jnp.float32)            # (N, DZ)
    c = c_ref[...].astype(jnp.float32)              # (N, DZ)
    step = step_ref[...]                            # (N, DZ)  ±η_c ⊙ mask
    # batched matvec: grad[i] = G[i] @ z[i].  G (N, DZ, DZ) is read from its
    # ref inside the loop: a value hoisted out of it would be a second
    # VMEM copy of the largest operand.
    gdims = (((2,), (1,)), ((0,), (0,)))

    # f32 operands contract at f32 precision (not the MXU's one bf16 pass)
    exact = jax.lax.Precision.HIGHEST

    def body(k, z):
        grad = jax.lax.dot_general(g_ref[...], z, gdims, precision=exact,
                                   preferred_element_type=jnp.float32)
        return z - step * (grad + h_ref[k] + c)

    zk = jax.lax.fori_loop(0, k_steps, body, z0)
    delta = zk - z0

    ef = ef_ref[...].astype(jnp.float32)
    if compress is None:
        q = delta                                    # mask already in step ⇒
        e_new = ef                                   # inactive Δ ≡ 0 exactly
    else:
        mask = mask_ref[...]
        v = mask * (delta + ef)                      # inactive: nothing on wire
        q = quantize_dequant(v, compress)
        e_new = jnp.where(mask > 0, v - q, ef)       # inactive residual frozen

    w = w_ref[...].astype(jnp.float32)               # (N, N)
    if gossip_dtype is None:
        wg, qg, zg = w, q, z0
    else:
        wg = w.astype(gossip_dtype)
        qg = q.astype(gossip_dtype)
        zg = z0.astype(gossip_dtype)
    wdims = (((1,), (0,)), ((), ()))
    wprec = exact if gossip_dtype is None else None
    wq = jax.lax.dot_general(wg, qg, wdims, precision=wprec,
                             preferred_element_type=jnp.float32)
    wz = jax.lax.dot_general(wg, zg, wdims, precision=wprec,
                             preferred_element_type=jnp.float32)
    z_out_ref[...] = wz + etas_ref[...] * wq
    c_out_ref[...] = c + corr_ref[...] * (q - wq)
    e_out_ref[...] = e_new


def fused_round_nd(w, z0, c, ef, g, h_steps, step, etas, corr, mask, *,
                   k_steps: int, compress=None, gossip_dtype=None,
                   interpret: bool):
    """w: (N, N); z0/c/ef/step/etas/corr/mask: (N, DZ) f32; g: (N, DZ, DZ);
    h_steps: (K, N, DZ).  N a sublane multiple, DZ a lane multiple (padding
    handled by ``ops.fused_round``).  Returns (z_new, c_new, ef_new) f32."""
    n, dz = z0.shape
    assert w.shape == (n, n), (w.shape, n)
    assert g.shape == (n, dz, dz), (g.shape, n, dz)
    assert h_steps.shape == (k_steps, n, dz), (h_steps.shape, k_steps, n, dz)
    for a in (c, ef, step, etas, corr, mask):
        assert a.shape == (n, dz), (a.shape, n, dz)

    kernel = functools.partial(_kernel, k_steps=k_steps, compress=compress,
                               gossip_dtype=gossip_dtype)
    out_sds = jax.ShapeDtypeStruct((n, dz), jnp.float32)
    # grid-less: every operand is one full VMEM block (see module docstring)
    return pl.pallas_call(
        kernel,
        out_shape=[out_sds, out_sds, out_sds],
        interpret=interpret,
    )(w, z0, c, ef, g, h_steps, step, etas, corr, mask)
