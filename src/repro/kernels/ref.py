"""Pure-jnp oracles for every Pallas kernel (token-by-token recurrences and
naive attention) — the ground truth the kernels are allclose-tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

#: The gossip oracles' contractions at full f32 precision: at its default a
#: TPU rounds f32 operands to bf16, which an oracle must not do.
_EXACT = jax.lax.Precision.HIGHEST


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0, scale=None):
    """q: (BH, Sq, D); k, v: (BKV, Sk, D); GQA by head-group replication."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qi = jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kj <= qi
    if window > 0:
        mask &= kj > qi - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(xdt, loga, bm, cm):
    """Token-by-token SSD recurrence.  xdt: (BH,S,P); loga: (BH,S);
    bm, cm: (B,S,N).  Returns y: (BH,S,P)."""
    bh, s, p = xdt.shape
    b, _, n = bm.shape
    heads = bh // b
    bmr = jnp.repeat(bm, heads, axis=0)
    cmr = jnp.repeat(cm, heads, axis=0)

    def step(state, inp):
        x_t, la_t, b_t, c_t = inp
        state = jnp.exp(la_t)[:, None, None] * state + jnp.einsum(
            "bp,bn->bpn", x_t.astype(jnp.float32), b_t.astype(jnp.float32))
        y_t = jnp.einsum("bn,bpn->bp", c_t.astype(jnp.float32), state)
        return state, y_t

    state0 = jnp.zeros((bh, p, n), jnp.float32)
    xs = (xdt.swapaxes(0, 1), loga.swapaxes(0, 1),
          bmr.swapaxes(0, 1), cmr.swapaxes(0, 1))
    _, ys = jax.lax.scan(step, state0, xs)
    return ys.swapaxes(0, 1).astype(xdt.dtype)


def fused_ce_ref(hidden, weight, labels):
    """Plain CE oracle: logits = hidden @ weight.T; NLL per token."""
    logits = hidden.astype(jnp.float32) @ weight.astype(jnp.float32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def fused_gossip_ref(w, delta, theta, c, eta_s, corr_scale, *,
                     gossip_dtype=None):
    """Packed round-epilogue oracle (Algorithm 1 lines 7–11 for one variable).

    w: (n, n); delta/theta/c: (n, D) f32.  Mirrors ``mixing.mix_dense``'s
    dtype rules: the matmul operands are narrowed to ``gossip_dtype`` (the
    communicated values), accumulation is f32, and Δ stays f32 inside the
    correction.  Returns (θ_new, c_new) = (Wθ + η_s·WΔ, c + s·(Δ − WΔ)).
    """
    w = jnp.asarray(w, jnp.float32)
    d32 = delta.astype(jnp.float32)
    t32 = theta.astype(jnp.float32)
    if gossip_dtype is None:
        wg, dg, tg = w, d32, t32
    else:
        wg = w.astype(gossip_dtype)
        dg = d32.astype(gossip_dtype)
        tg = t32.astype(gossip_dtype)
    wd = jnp.einsum("ij,jd->id", wg, dg, preferred_element_type=jnp.float32,
                    precision=_EXACT)
    wt = jnp.einsum("ij,jd->id", wg, tg, preferred_element_type=jnp.float32,
                    precision=_EXACT)
    theta_new = wt + eta_s * wd
    c_new = c.astype(jnp.float32) + corr_scale * (d32 - wd)
    return theta_new, c_new


def fused_round_ref(w, z0, c, ef, g, h_steps, step, etas, corr, mask, *,
                    compress=None, gossip_dtype=None):
    """Whole-round oracle (K affine local SGDA steps + gossip epilogue) —
    the ground truth for ``kernels/fused_round.py``.

    w: (n, n); z0/c/ef/step/etas/corr/mask: (n, dz) f32; g: (n, dz, dz);
    h_steps: (K, n, dz).  Semantics documented in the kernel module; the
    quantizer is the shared ``kernels.quantize.quantize_dequant`` so the
    lowerings cannot drift on rounding.  Returns (z_new, c_new, ef_new).
    """
    from repro.kernels.quantize import quantize_dequant

    z0 = z0.astype(jnp.float32)
    c32 = c.astype(jnp.float32)

    def body(z, h):
        grad = jnp.einsum("nij,nj->ni", g, z,
                          preferred_element_type=jnp.float32,
                          precision=_EXACT)
        return z - step * (grad + h + c32), None

    zk, _ = jax.lax.scan(body, z0, h_steps)
    delta = zk - z0
    ef32 = ef.astype(jnp.float32)
    if compress is None:
        q, e_new = delta, ef32
    else:
        v = mask * (delta + ef32)
        q = quantize_dequant(v, compress)
        e_new = jnp.where(mask > 0, v - q, ef32)
    w32 = jnp.asarray(w, jnp.float32)
    if gossip_dtype is None:
        wg, qg, zg = w32, q, z0
    else:
        wg = w32.astype(gossip_dtype)
        qg = q.astype(gossip_dtype)
        zg = z0.astype(gossip_dtype)
    wq = jnp.einsum("ij,jd->id", wg, qg, preferred_element_type=jnp.float32,
                    precision=_EXACT)
    wz = jnp.einsum("ij,jd->id", wg, zg, preferred_element_type=jnp.float32,
                    precision=_EXACT)
    return wz + etas * wq, c32 + corr * (q - wq), e_new


def sparse_gossip_ref(neighbor_idx, neighbor_w, self_w, delta, theta, c,
                      eta_s, corr_scale, *, gossip_dtype=None):
    """Sparse (neighbor-list) round-epilogue oracle — same epilogue as
    ``fused_gossip_ref`` with W given in padded-CSR form.

    neighbor_idx: (n, m) int32 (padding = own index); neighbor_w: (n, m)
    with padding weight 0; self_w: (n,) diagonal; delta/theta/c: (n, D).
    Raw arrays (not a ``SparseTopology``) so the kernels package stays free
    of core imports.  Mirrors the dense oracle's dtype rules: weights and
    communicated values narrow to ``gossip_dtype``, products accumulate in
    f32, Δ stays f32 inside the correction.
    """
    d32 = delta.astype(jnp.float32)
    t32 = theta.astype(jnp.float32)
    if gossip_dtype is None:
        dg, tg = d32, t32
        nwg = neighbor_w.astype(jnp.float32)
        swg = self_w.astype(jnp.float32)
    else:
        dg = d32.astype(gossip_dtype)
        tg = t32.astype(gossip_dtype)
        nwg = neighbor_w.astype(gossip_dtype)
        swg = self_w.astype(gossip_dtype)

    def spmv(x):
        # self term first, then one slot at a time: the neighbor-gather
        # kernel's summation order, so the two agree to the ulp
        x32 = x.astype(jnp.float32)
        acc = swg.astype(jnp.float32)[:, None] * x32
        for slot in range(neighbor_idx.shape[1]):
            acc = acc + (nwg[:, slot].astype(jnp.float32)[:, None]
                         * jnp.take(x32, neighbor_idx[:, slot], axis=0))
        return acc

    wd = spmv(dg)
    theta_new = spmv(tg) + eta_s * wd
    c_new = c.astype(jnp.float32) + corr_scale * (d32 - wd)
    return theta_new, c_new


def robust_agg_ref(vals, valid, *, rule, trim: int = 1):
    """Robust-aggregation oracle (coordinate median / b-trimmed mean over
    each row's valid slots) — the ground truth ``mixing.robust_mix_dense``
    and ``robust_mix_sparse`` are parity-tested against.

    vals: (n, m, D) candidate values; valid: (n, m) bool with ≥ 1 valid
    slot per row.  Non-finite values are invalid per coordinate (a diverged
    attacker must not consume a trim slot — ``mixing._robust_reduce``'s
    contract).  Deliberately a *different* float path from the
    implementations: the median goes through ``jnp.nanmedian`` and the
    trimmed mean sorts **descending** (so the surviving values accumulate
    in the reverse order), which makes agreement a real check rather than
    the same expression twice.
    """
    v32 = vals.astype(jnp.float32)
    ok = valid[:, :, None] & jnp.isfinite(v32)
    if rule == "coord_median":
        return jnp.nanmedian(jnp.where(ok, v32, jnp.nan), axis=1)
    if rule != "trimmed_mean":
        raise ValueError(f"unknown robust rule {rule!r}")
    n, m, d = vals.shape
    k = ok.sum(1).astype(jnp.int32)                          # (n, D)
    b = jnp.minimum(jnp.int32(trim), (k - 1) // 2)
    # invalid -> -inf, ascending sort, reverse: valid descending, pad last
    desc = jnp.sort(jnp.where(ok, v32, -jnp.inf), axis=1)[:, ::-1]
    rank = jnp.arange(m, dtype=jnp.int32)[None, :, None]
    keep = (rank >= b[:, None, :]) & (rank < (k - b)[:, None, :])
    total = jnp.sum(jnp.where(keep, desc, 0.0), axis=1)
    return total / (k - 2 * b).astype(jnp.float32)


def rglru_ref(a, u):
    """Token-by-token h_t = a_t h_{t-1} + u_t.  a, u: (B,S,W)."""

    def step(h, inp):
        a_t, u_t = inp
        h = a_t * h + u_t
        return h, h

    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, hs = jax.lax.scan(
        step, h0, (a.swapaxes(0, 1).astype(jnp.float32),
                   u.swapaxes(0, 1).astype(jnp.float32)))
    return hs.swapaxes(0, 1).astype(a.dtype)
