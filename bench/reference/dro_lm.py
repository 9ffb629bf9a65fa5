"""Plain reference of the DRO language-model job: the configuration's
decoder (GQA attention with RoPE, SwiGLU MLP, RMSNorm, tied head) in
float32, the group-DRO objective ``f_i(x, y) = Σ_g y_g ℓ_g(x; D_i) −
μ/2 ‖y‖²``, and Algorithm 1 of K-GT-Minimax on a fixed mixing matrix.

It follows the published algorithm and the configuration file, and takes
nothing from the program under test: weights, data and keys are drawn here
from the seed by the same schedule the trainer uses (see
``reference/data.py``).  Every matrix product goes through
:class:`Numerics`: float32 at ``Precision.HIGHEST`` for the reference, or
operands rounded to a narrower type for the control.  Clients run one
after another, so the activations of one client's step live at a time.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference import data as data_ref

HIGHEST = jax.lax.Precision.HIGHEST


class Numerics:
    """How matrix products are computed.

    ``"float32"``: in float32 (``Precision.HIGHEST``), the reference.
    ``"high"``: float32 products as three bf16 passes (each operand split
    into a bf16 high part and a bf16 remainder; the remainder × remainder
    term dropped), written out so that it means the same on every backend.
    Any other name (``"bfloat16"``, ``"float8_e4m3fn"``): both operands
    rounded to that type, then multiplied and accumulated in float32.
    """

    def __init__(self, precision: str = "float32"):
        self.precision = precision

    def _round(self, t):
        return t.astype(jnp.dtype(self.precision)).astype(jnp.float32)

    def dot(self, spec, a, b):
        def ein(x, y):
            return jnp.einsum(spec, x, y, precision=HIGHEST,
                              preferred_element_type=jnp.float32)

        if self.precision == "float32":
            return ein(a, b)
        if self.precision == "high":
            a_hi = a.astype(jnp.bfloat16).astype(jnp.float32)
            b_hi = b.astype(jnp.bfloat16).astype(jnp.float32)
            a_lo = (a - a_hi).astype(jnp.bfloat16).astype(jnp.float32)
            b_lo = (b - b_hi).astype(jnp.bfloat16).astype(jnp.float32)
            return ein(a_hi, b_hi) + ein(a_hi, b_lo) + ein(a_lo, b_hi)
        return ein(self._round(a), self._round(b))

    def __hash__(self):
        return hash(self.precision)

    def __eq__(self, other):
        return (isinstance(other, Numerics)
                and other.precision == self.precision)


LEAVES = ("embed", "final_norm", "norm1", "norm2", "attn.wq", "attn.wk",
          "attn.wv", "attn.wo", "mlp.gate", "mlp.up", "mlp.down")


def _normal_scaled(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / jnp.sqrt(fan_in))


def init_params(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    """Weights as the trainer draws them: embedding N(0, 0.02²); each
    projection N(0, 1/fan_in) with fan-in its first axis (its second-to-last
    for the MLP); norm scales zero (the norm multiplies by 1 + scale);
    layer r's keys from ``split(fold_in(stack key, 0), L)[r]``."""
    d, h, kv = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd, ff, L = cfg["head_dim"], cfg["d_ff"], cfg["num_layers"]
    k_embed, k_stack, _ = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(jax.random.fold_in(k_stack, 0), L):
        keys = jax.random.split(lk, 4)
        kq, kk, kv_, ko = jax.random.split(keys[0], 4)
        k1, k2, k3 = jax.random.split(keys[1], 3)
        layers.append({
            "attn.wq": _normal_scaled(kq, (d, h, hd), d),
            "attn.wk": _normal_scaled(kk, (d, kv, hd), d),
            "attn.wv": _normal_scaled(kv_, (d, kv, hd), d),
            "attn.wo": _normal_scaled(ko, (h, hd, d), h),
            "mlp.gate": _normal_scaled(k1, (d, ff), d),
            "mlp.up": _normal_scaled(k2, (d, ff), d),
            "mlp.down": _normal_scaled(k3, (ff, d), ff),
            "norm1": jnp.zeros((d,)),
            "norm2": jnp.zeros((d,)),
        })
    params = {k: jnp.stack([l[k] for l in layers]) for k in layers[0]}
    params["embed"] = jax.random.normal(k_embed, (cfg["vocab_size"], d)) * 0.02
    params["final_norm"] = jnp.zeros((d,))
    return params


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def token_nll(cfg: dict, num: Numerics, params, tokens, labels):
    """Per-token negative log-likelihood ``(B, S)`` of next-token labels."""
    b, s = tokens.shape
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens]

    def layer(x, p):
        a = _rms_norm(x, p["norm1"], eps)
        q = _rope(num.dot("bsd,dhk->bshk", a, p["attn.wq"]), pos, theta)
        k = _rope(num.dot("bsd,dhk->bshk", a, p["attn.wk"]), pos, theta)
        v = num.dot("bsd,dhk->bshk", a, p["attn.wv"])
        qg = q.reshape(b, s, kv, h // kv, hd)
        logits = num.dot("bqhgd,bkhd->bhgqk", qg, k) * hd ** -0.5
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = num.dot("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, h, hd)
        x = x + num.dot("bshk,hkd->bsd", ctx, p["attn.wo"])
        m = _rms_norm(x, p["norm2"], eps)
        gate = num.dot("bsd,df->bsf", m, p["mlp.gate"])
        up = num.dot("bsd,df->bsf", m, p["mlp.up"])
        x = x + num.dot("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp.down"])
        return x, None

    stack = {k: params[k] for k in params if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(layer, x, stack)
    x = _rms_norm(x, params["final_norm"], eps)
    logits = num.dot("bsd,vd->bsv", x, params["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def group_losses(cfg, num, params, batch, num_groups):
    nll = token_nll(cfg, num, params, batch["tokens"], batch["labels"])
    onehot = jax.nn.one_hot(batch["groups"], num_groups, dtype=jnp.float32)
    sums = jnp.sum(nll[..., None] * onehot, axis=(0, 1))
    counts = jnp.maximum(onehot.sum((0, 1)), 1.0)
    return sums / counts


def dro_value(cfg, num, num_groups, mu, x, y, batch):
    losses = group_losses(cfg, num, x, batch, num_groups)
    return jnp.dot(y, losses, precision=HIGHEST) - 0.5 * mu * jnp.sum(y * y)


def consensus(tree) -> jnp.ndarray:
    """(1/n) Σ_i ‖T_i − T̄‖² summed over leaves."""
    tot = 0.0
    for leaf in jax.tree.leaves(tree):
        m = leaf.mean(0, keepdims=True)
        tot = tot + jnp.sum(jnp.square(leaf - m)) / leaf.shape[0]
    return tot


class Job:
    """One K-GT-Minimax DRO job as the cell's traffic file states it,
    computed plainly under ``num``."""

    def __init__(self, cfg: dict, traffic: dict, num: Numerics):
        self.cfg, self.tr, self.num = cfg, traffic, num
        self.n = traffic["clients"]
        self.k = traffic["local_steps"]
        self.groups = traffic["groups"]
        self.mu = traffic["mu"]
        self.w = jnp.asarray(np.asarray(traffic["mixing_matrix"]),
                             jnp.float32)
        eta = traffic["eta"]
        self.eta_cx, self.eta_cy = eta["cx"], eta["cy"]
        self.eta_sx = self.eta_sy = eta["s"]
        self.corr_x = np.float32(1.0 / (self.k * self.eta_cx))
        self.corr_y = np.float32(-1.0 / (self.k * self.eta_cy))
        self._grads = jax.jit(self._grads_fn)
        self._round = jax.jit(self._round_fn, donate_argnums=(0,))
        self._metrics = jax.jit(self._metrics_fn)
        self._batches = jax.jit(functools.partial(
            data_ref.round_batches, local_steps=self.k, num_clients=self.n,
            batch=traffic["batch"], seq_len=traffic["seq_len"]))

    def _mix(self, tree):
        return jax.tree.map(
            lambda t: jnp.einsum("ij,j...->i...", self.w, t,
                                 precision=HIGHEST), tree)

    def _grads_fn(self, x, y, batch):
        return jax.grad(functools.partial(
            dro_value, self.cfg, self.num, self.groups, self.mu),
            argnums=(0, 1))(x, y, batch)

    def _round_fn(self, state, batches):
        x, y, cx, cy = state

        def local(args):
            xi, yi, cxi, cyi, bi = args
            for step in range(self.k):
                b = jax.tree.map(lambda t: t[step], bi)
                gx, gy = self._grads_fn(xi, yi, b)
                gx = jax.tree.map(lambda c, g: c + g, cxi, gx)
                gy = cyi + gy
                xi = jax.tree.map(lambda g, p: -self.eta_cx * g + p, gx, xi)
                yi = self.eta_cy * gy + yi
            return xi, yi

        per_client = jax.tree.map(lambda t: jnp.swapaxes(t, 0, 1), batches)
        xk, yk = jax.lax.map(local, (x, y, cx, cy, per_client))
        dx = jax.tree.map(lambda a, b: a - b, xk, x)
        dy = yk - y
        mdx, mx = self._mix(dx), self._mix(x)
        mdy, my = self._mix(dy), self._mix(y)
        cx = jax.tree.map(lambda c, d, md: self.corr_x * (d - md) + c,
                          cx, dx, mdx)
        cy = self.corr_y * (dy - mdy) + cy
        x = jax.tree.map(lambda m, md: self.eta_sx * md + m, mx, mdx)
        y = self.eta_sy * mdy + my
        return x, y, cx, cy

    def _metrics_fn(self, state, batches, eval_b):
        x, y, _, _ = state
        xbar = jax.tree.map(lambda t: t.mean(0), x)
        ybar = y.mean(0)
        train_b = jax.tree.map(lambda t: t[0, 0], batches)
        tl = group_losses(self.cfg, self.num, xbar, train_b, self.groups)
        el = group_losses(self.cfg, self.num, xbar, eval_b, self.groups)
        return {
            "f_bar": jnp.dot(ybar, tl, precision=HIGHEST)
            - 0.5 * self.mu * jnp.sum(ybar * ybar),
            "mean_loss": tl.mean(),
            "eval_loss": el.mean(),
            "consensus_x": consensus(x),
            "consensus_y": consensus(y),
            "y_bar_norm": jnp.sqrt(jnp.sum(jnp.square(ybar))),
        }

    def init(self, seed: int):
        """``(state, data model, round key, held-out batch)`` from ``seed``."""
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
        x0 = init_params(self.cfg, kx)
        x = jax.tree.map(lambda t: jnp.broadcast_to(t[None], (self.n,) + t.shape),
                         x0)
        y = jnp.zeros((self.n, self.groups))
        gx, gy = [], []
        for i in range(self.n):
            xi = jax.tree.map(lambda t: t[i], x)
            bi = jax.tree.map(lambda t: t[i], init_b)
            g = self._grads(xi, y[i], bi)
            gx.append(g[0])
            gy.append(g[1])
        gx = jax.tree.map(lambda *t: jnp.stack(t), *gx)
        gy = jnp.stack(gy)
        cx = jax.tree.map(lambda g: g.mean(0, keepdims=True) - g, gx)
        cy = gy.mean(0, keepdims=True) - gy
        return (x, y, cx, cy), dm, kt, eval_b

    def follow(self, seed: int, steps: int, rounds_per_step: int,
               log_every: int) -> dict:
        """Run ``steps`` calls' worth of rounds from ``seed``; return what
        the comparison reads: the metric rows of the logged rounds, the
        per-leaf norms of ``cx`` after step 1 and of the change of ``x``
        from step 1 to the last step."""
        state, dm, kt, eval_b = self.init(seed)
        rows: List[dict] = []
        after_first = None
        for r in range(steps * rounds_per_step):
            batches = self._batches(dm, jax.random.fold_in(kt, r))
            state = self._round(state, batches)
            if r % log_every == 0:
                m = jax.device_get(self._metrics(state, batches, eval_b))
                rows.append({"round": r, **{k: float(v) for k, v in m.items()}})
            if r + 1 == rounds_per_step:
                after_first = jax.device_get(state[0])
                corr_norms = leaf_norms(state[2])
        change = leaf_change_norms(after_first, jax.device_get(state[0]))
        return {"rows": rows, "corr_norms": corr_norms,
                "change_norms": change}


def leaf_norms(tree) -> Dict[str, float]:
    """``{leaf name: ‖leaf‖}`` over all clients' rows."""
    return {name: float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(leaf, jnp.float32))))) for name, leaf in tree.items()}


def leaf_change_norms(before, after) -> Dict[str, float]:
    """``{leaf name: ‖after − before‖}`` computed on the host."""
    out = {}
    for name in before:
        d = (np.asarray(after[name]) - np.asarray(before[name])).ravel()
        out[name] = float(np.sqrt(np.dot(d, d)))
    return out
