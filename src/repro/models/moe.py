"""Mixture-of-Experts MLP with top-k routing, in two dispatches.

* ``moe_mlp_sorted`` (``MoEConfig.dispatch = "sorted"``): dropless.  The
  layer routes every token over all ``num_experts`` and computes the part
  of the result that the experts it holds give (``MoEConfig.held``, the
  first ones): the (token, choice) rows routed to a held expert are sorted
  by expert and run through grouped products (``jax.lax.ragged_dot``);
  rows routed to an absent expert add nothing.  This is the training path
  of one chip's share of an expert-parallel deployment
  (``granite-moe-1b-a400m-ep4``).
* ``moe_mlp`` (``"dense"``): the one-hot capacity formulation
  (Switch/GShard style) with static shapes, which GSPMD shards over the
  ``model`` axis with ``moe_expert_parallel`` (the dry runs).  Rows past
  an expert's capacity are dropped (the residual passes through).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist import context as dist_ctx
from repro.models.layers import dense_init


def init_moe(key, cfg, d_model: int):
    m = cfg.moe
    kr, kg, ku, kd = jax.random.split(key, 4)
    e, f = m.held, m.expert_d_ff
    return {
        "router": dense_init(kr, (d_model, m.num_experts), in_axis=0),
        "gate": dense_init(kg, (e, d_model, f), in_axis=1),
        "up": dense_init(ku, (e, d_model, f), in_axis=1),
        "down": dense_init(kd, (e, f, d_model), in_axis=1),
    }


def capacity(num_tokens: int, num_experts: int, top_k: int, factor: float = 1.25) -> int:
    return max(4, int(num_tokens * top_k / num_experts * factor))


def moe_mlp(params, x, cfg, compute_dtype=jnp.bfloat16):
    """x: (B, S, d).  Returns (out, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.num_experts, m.top_k
    # per-batch-row capacity keeps shapes batch-invariant
    cap = capacity(s, e, k, m.capacity_factor)

    xt = x.reshape(b, s, d)
    logits = jnp.einsum("bsd,de->bse", xt, params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (b,s,e)

    # top-k selection
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (b,s,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance auxiliary loss (Switch): e * <frac_tokens_e, frac_prob_e>
    me = probs.mean(axis=(0, 1))  # (e,)
    one_hot_top1 = jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=(0, 1))
    aux = m.router_aux_coef * e * jnp.sum(me * ce)

    # Position of each (token, choice) within its expert's capacity buffer.
    sel = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (b,s,k,e)
    flat_sel = sel.reshape(b, s * k, e)
    pos = jnp.cumsum(flat_sel, axis=1) * flat_sel - 1  # (b, s*k, e), -1 if unselected
    pos = pos.reshape(b, s, k, e)
    in_cap = (pos >= 0) & (pos < cap)

    # combine[b,s,k,e,c]: weight routing token (b,s) choice k to slot c of expert e
    combine = (
        gate_vals[..., None, None]
        * in_cap[..., None]
        * jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap, dtype=jnp.float32)
        * sel[..., None].astype(jnp.float32)
    )
    combine = combine.sum(axis=2)  # (b,s,e,c)
    dispatch = (combine > 0).astype(compute_dtype)

    xe = jnp.einsum("bsec,bsd->becd", dispatch, xt.astype(compute_dtype))
    w = lambda p: p.astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w(params["gate"])))
    h = h * jnp.einsum("becd,edf->becf", xe, w(params["up"]))
    ye = jnp.einsum("becf,efd->becd", h, w(params["down"]))
    out = jnp.einsum("bsec,becd->bsd", combine.astype(compute_dtype), ye)
    return out.astype(x.dtype), aux


def route(router, xt, m):
    """Router over all experts, in float32: ``(top-k expert ids (T, k),
    gates (T, k), Switch load-balance loss)``.  The gates are the softmax
    over the top-k logits, which is the renormalized top-k of the full
    softmax."""
    e, k = m.num_experts, m.top_k
    logits = jnp.einsum("nd,de->ne", xt, router.astype(jnp.float32))
    top_logits, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    ce = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32).mean(axis=0)
    aux = m.router_aux_coef * e * jnp.sum(probs.mean(axis=0) * ce)
    return idx, gates, aux


def _per_client(fn):
    """``fn`` as is, and under ``vmap`` once per client on static slices:
    XLA's TPU ragged-dot takes no batch dimension.  Where the clients axis
    is split over devices (``dist_ctx.CLIENTS_SHARDED``), a static slice of
    it moves each client's operands between devices (collective permutes),
    so there the products stay batched, which only the CPU compiles
    (``launch.steps`` refuses such a TPU mesh)."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        if dist_ctx.has(dist_ctx.CLIENTS_SHARDED):
            axes = [0 if b else None for b in in_batched]
            out = jax.vmap(fn, in_axes=axes)(*args)
        else:
            outs = [f(*[a[c] if b else a for a, b in zip(args, in_batched)])
                    for c in range(axis_size)]
            out = jax.tree.map(lambda *o: jnp.stack(o), *outs)
        return out, jax.tree.map(lambda _: True, out)

    return f


@_per_client
def _gmm_fwd_product(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes)


@_per_client
def _gmm_bwd_product(xs, w, sizes, g):
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), xs, w)
    return vjp(g)


@jax.custom_vjp
def grouped_matmul(xs, w, sizes):
    """``(R, d) × (G, d, f) -> (R, f)``: row block g (``sizes[g]`` rows, in
    order) times ``w[g]``; rows past ``sum(sizes)`` belong to no group."""
    return _gmm_fwd_product(xs, w, sizes)


def _gmm_fwd(xs, w, sizes):
    return _gmm_fwd_product(xs, w, sizes), (xs, w, sizes)


def _gmm_bwd(res, g):
    xs, w, sizes = res
    with jax.named_scope("moe.experts"):
        dxs, dw = _gmm_bwd_product(xs, w, sizes, g)
    return dxs, dw, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def moe_mlp_sorted(params, x, cfg, compute_dtype=jnp.bfloat16):
    """Dropless sorted dispatch over the held experts.  x: (B, S, d).
    Returns ``(out, aux, stats)``: ``stats["rows"]`` (held,) rows routed to
    each held expert, ``stats["held"]`` (B·S, held) which held experts each
    token picked.

    ``params["gate"|"up"|"down"]`` hold the ``held`` experts' weights; the
    router keeps all ``num_experts`` outputs.  Every (token, choice) row is
    kept: rows routed to a held expert sort first, by expert, and only they
    enter the grouped products; the rest sort last and add nothing."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    k, held = m.top_k, m.held
    xt = x.reshape(n, d)
    w = lambda p: p.astype(compute_dtype)

    with jax.named_scope("moe.route"):
        idx, gates, aux = route(params["router"], xt, m)
        flat = idx.reshape(-1)                         # (n·k,)
        group = jnp.where(flat < held, flat, held)     # absent experts last
        order = jnp.argsort(group, stable=True)
        tok_of = order // k
        valid = (group < held)[order]
        sizes = jnp.bincount(group, length=held + 1)[:held]
        xs = jnp.where(valid[:, None], xt[tok_of].astype(compute_dtype), 0)

    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(grouped_matmul(xs, w(params["gate"]), sizes))
        h = h * grouped_matmul(xs, w(params["up"]), sizes)
        ys = grouped_matmul(h, w(params["down"]), sizes)     # (n·k, d)

    with jax.named_scope("moe.combine"):
        g = jnp.where(valid, gates.reshape(-1)[order], 0.0)
        contrib = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0)
        out = jnp.zeros((n, d), jnp.float32).at[tok_of].add(
            contrib * g[:, None])
    out = out.reshape(b, s, d).astype(x.dtype)
    picked = (idx[:, :, None] == jnp.arange(held)).any(axis=1)
    return out, aux, {"rows": sizes, "held": picked}
