"""Plain reference of the DRO job on one chip's share of a sparse-expert
decoder (``granite-moe-1b-a400m-ep4``): Granite's block (GQA attention with
RoPE, then a router over all experts and SwiGLU experts, RMSNorm, tied
head, its four scalar multipliers) in float32, the group-DRO objective and
Algorithm 1 as ``dro_lm`` has them.

It follows the published model and the configuration file, and takes
nothing from the program under test.  It reuses ``dro_lm``'s
:class:`~reference.dro_lm.Numerics` (every matrix product goes through it:
float32 at ``Precision.HIGHEST``, or operands rounded for the control),
its data schedule and its Algorithm 1; weights are drawn here by the
trainer's schedule.  Each held expert is computed on every token and
masked by the one-hot routing: no sort and no grouped product.

Departures from Granite's published forward, all of them the share's:

* the router keeps all ``num_experts`` outputs and its top-k, but only the
  first ``experts_held`` experts exist here: what the absent experts would
  add is left out, and that partial result goes on to the next layer;
* the attention has ``num_heads``/``num_kv_heads`` of the published heads
  and no exchange with the other shards' heads;
* the vocabulary is the first ``vocab_size`` rows: tokens, logits and loss
  are over the slice;
* the Switch load-balance loss (coefficient ``router_aux_coef``, summed
  over layers) is added to every client's objective, as the program does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import data as data_ref
from reference import dro_lm
from reference.dro_lm import HIGHEST, Numerics, _normal_scaled, _rms_norm, _rope

__all__ = ["Job", "Numerics", "init_params", "forward", "group_losses"]


def init_params(cfg: dict, key):
    """Weights as the trainer draws them: ``dro_lm.init_params``'s schedule,
    with each layer's experts from its second key: router N(0, 1/d) over
    all experts, gate and up N(0, 1/d), down N(0, 1/expert width)."""
    d, h, kv = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd, L = cfg["head_dim"], cfg["num_layers"]
    e, held, f = cfg["num_experts"], cfg["experts_held"], cfg["expert_d_ff"]
    k_embed, k_stack, _ = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(jax.random.fold_in(k_stack, 0), L):
        keys = jax.random.split(lk, 4)
        kq, kk, kv_, ko = jax.random.split(keys[0], 4)
        kr, kg, ku, kd = jax.random.split(keys[1], 4)
        layers.append({
            "attn.wq": _normal_scaled(kq, (d, h, hd), d),
            "attn.wk": _normal_scaled(kk, (d, kv, hd), d),
            "attn.wv": _normal_scaled(kv_, (d, kv, hd), d),
            "attn.wo": _normal_scaled(ko, (h, hd, d), h),
            "moe.router": _normal_scaled(kr, (d, e), d),
            "moe.gate": _normal_scaled(kg, (held, d, f), d),
            "moe.up": _normal_scaled(ku, (held, d, f), d),
            "moe.down": _normal_scaled(kd, (held, f, d), f),
            "norm1": jnp.zeros((d,)),
            "norm2": jnp.zeros((d,)),
        })
    params = {k: jnp.stack([l[k] for l in layers]) for k in layers[0]}
    params["embed"] = jax.random.normal(k_embed, (cfg["vocab_size"], d)) * 0.02
    params["final_norm"] = jnp.zeros((d,))
    return params


def _experts(cfg, num: Numerics, p, m):
    """The held experts' part of the layer for every token ``m`` (B, S, d):
    ``(out, Switch loss, held experts each token picked (B·S, held))``."""
    e, held, k = cfg["num_experts"], cfg["experts_held"], cfg["top_k"]
    logits = num.dot("bsd,de->bse", m, p["moe.router"])
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    picked = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # (B,S,k,E)
    weight = jnp.einsum("bsk,bske->bse", gates, picked,
                        precision=HIGHEST)[..., :held]
    g = num.dot("bsd,hdf->bshf", m, p["moe.gate"])
    u = num.dot("bsd,hdf->bshf", m, p["moe.up"])
    act = jax.nn.silu(g) * u * weight[..., None]
    out = num.dot("bshf,hfd->bsd", act, p["moe.down"])
    probs = jax.nn.softmax(logits, axis=-1)
    ce = picked[:, :, 0].mean((0, 1))
    aux = cfg["router_aux_coef"] * e * jnp.sum(probs.mean((0, 1)) * ce)
    held_set = picked.sum(2)[..., :held].reshape(-1, held) > 0
    return out, aux, held_set


def forward(cfg: dict, num: Numerics, params, tokens):
    """``(logits (B, S, V), Switch loss, held sets (L, B·S, held))``."""
    b, s = tokens.shape
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    res = cfg["residual_multiplier"]
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens] * cfg["embedding_multiplier"]

    def layer(carry, p):
        x, aux = carry
        a = _rms_norm(x, p["norm1"], eps)
        q = _rope(num.dot("bsd,dhk->bshk", a, p["attn.wq"]), pos, theta)
        k = _rope(num.dot("bsd,dhk->bshk", a, p["attn.wk"]), pos, theta)
        v = num.dot("bsd,dhk->bshk", a, p["attn.wv"])
        qg = q.reshape(b, s, kv, h // kv, hd)
        scores = (num.dot("bqhgd,bkhd->bhgqk", qg, k)
                  * cfg["attention_multiplier"])
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        ctx = num.dot("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, h, hd)
        x = x + res * num.dot("bshk,hkd->bsd", ctx, p["attn.wo"])
        out, la, held_set = _experts(cfg, num, p,
                                     _rms_norm(x, p["norm2"], eps))
        return (x + res * out, aux + la), held_set

    stack = {k: params[k] for k in params if k not in ("embed", "final_norm")}
    (x, aux), held = jax.lax.scan(layer, (x, jnp.zeros(())), stack)
    x = _rms_norm(x, params["final_norm"], eps)
    logits = num.dot("bsd,vd->bsv", x, params["embed"]) / cfg["logits_scaling"]
    return logits, aux, held


def group_losses(cfg, num, params, batch, num_groups):
    """``((G,) group losses, Switch loss)``."""
    logits, aux, _ = forward(cfg, num, params, batch["tokens"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    onehot = jax.nn.one_hot(batch["groups"], num_groups, dtype=jnp.float32)
    sums = jnp.sum(nll[..., None] * onehot, axis=(0, 1))
    return sums / jnp.maximum(onehot.sum((0, 1)), 1.0), aux


def dro_value(cfg, num, num_groups, mu, x, y, batch):
    losses, aux = group_losses(cfg, num, x, batch, num_groups)
    return (jnp.dot(y, losses, precision=HIGHEST) + aux
            - 0.5 * mu * jnp.sum(y * y))


class Job(dro_lm.Job):
    """``dro_lm.Job`` (Algorithm 1, data, metrics) on the share's model."""

    def _grads_fn(self, x, y, batch):
        return jax.grad(lambda x, y: dro_value(
            self.cfg, self.num, self.groups, self.mu, x, y, batch),
            argnums=(0, 1))(x, y)

    def _metrics_fn(self, state, batches, eval_b):
        x, y, _, _ = state
        xbar = jax.tree.map(lambda t: t.mean(0), x)
        ybar = y.mean(0)
        train_b = jax.tree.map(lambda t: t[0, 0], batches)
        tl, aux = group_losses(self.cfg, self.num, xbar, train_b, self.groups)
        el, _ = group_losses(self.cfg, self.num, xbar, eval_b, self.groups)
        return {
            "f_bar": jnp.dot(ybar, tl, precision=HIGHEST) + aux
            - 0.5 * self.mu * jnp.sum(ybar * ybar),
            "mean_loss": tl.mean(),
            "eval_loss": el.mean(),
            "consensus_x": dro_lm.consensus(x),
            "consensus_y": dro_lm.consensus(y),
            "y_bar_norm": jnp.sqrt(jnp.sum(jnp.square(ybar))),
        }

    def draws(self, seed: int):
        """``(x0, data model, round key, held-out batch, init batch)``
        from ``seed``, by the trainer's schedule."""
        tr = self.tr
        kd, ki, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
        dm = data_ref.data_model(
            kd, vocab_size=self.cfg["vocab_size"], num_groups=self.groups,
            num_clients=self.n, alpha=tr["alpha"])
        init_b = jax.tree.map(lambda t: t[0], data_ref.round_batches(
            dm, jax.random.fold_in(kd, 1), local_steps=1,
            num_clients=self.n, batch=tr["batch"], seq_len=tr["seq_len"]))
        eval_b = data_ref.eval_batch(
            dm, jax.random.fold_in(kd, 2), num_clients=self.n,
            batch=tr["batch"], seq_len=tr["seq_len"])
        kx, _, _ = jax.random.split(ki, 3)
        return init_params(self.cfg, kx), dm, kt, eval_b, init_b

    def init(self, seed: int):
        x0, dm, kt, eval_b, init_b = self.draws(seed)
        x = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (self.n,) + t.shape), x0)
        y = jnp.zeros((self.n, self.groups))
        gx, gy = [], []
        for i in range(self.n):
            g = self._grads(jax.tree.map(lambda t: t[i], x), y[i],
                            jax.tree.map(lambda t: t[i], init_b))
            gx.append(g[0])
            gy.append(g[1])
        gx = jax.tree.map(lambda *t: jnp.stack(t), *gx)
        gy = jnp.stack(gy)
        cx = jax.tree.map(lambda g: g.mean(0, keepdims=True) - g, gx)
        cy = gy.mean(0, keepdims=True) - gy
        return (x, y, cx, cy), dm, kt, eval_b

    def first_step_held(self, seed: int):
        """``(x0, first local step's batches (n, B, S), held sets of each
        client (n, L, B·S, held))``: what the reference routes in round 0's
        first local step."""
        x0, dm, kt, _, _ = self.draws(seed)
        batches = jax.tree.map(lambda t: t[0], self._batches(
            dm, jax.random.fold_in(kt, 0)))
        # the weights are an argument: closed over, they would be compiled in
        held = jax.jit(jax.vmap(
            lambda p, tok: forward(self.cfg, self.num, p, tok)[2],
            in_axes=(None, 0)))(x0, batches["tokens"])
        return x0, batches, np.asarray(held)
