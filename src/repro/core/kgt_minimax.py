"""K-GT-Minimax (Algorithm 1) and its baselines, as pure JAX transforms.

State layout: every variable carries a leading clients dim ``n`` —
``x: (n, …)`` pytree, ``y: (n, …)``, corrections ``cx, cy`` likewise.  The
per-client gradient oracle is vmapped over that dim; on the decentralized
mesh the dim is sharded over the ``clients`` axis so each client's compute
stays on its own sub-mesh and only mixing communicates across clients.

One ``round_step`` = one communication round of Algorithm 1:

  1. K local steps        x_i -= η_cx (∇x F_i + c_i^x);  y_i += η_cy (∇y F_i + c_i^y)
  2. correction update    c_i^x += (Δx_i − (WΔx)_i)/(K η_cx)   [line 7; Σ_j(δ−w)Δx_j]
                          c_i^y −= (Δy_i − (WΔy)_i)/(K η_cy)   [line 8]
  3. parameter mixing     x_i ← Σ_j w_ij (x_j + η_sx Δx_j)     [line 10]
                          y_i ← Σ_j w_ij (y_j + η_sy Δy_j)     [line 11]

Baselines (same harness, for Table-1 comparisons):
  * ``dsgda``      decentralized SGDA: K=1, no tracking  (DM-HSGD-family ancestor)
  * ``local_sgda`` K local steps + mixing, no tracking   (Fed-Norm-SGDA-like)
  * ``gt_gda``     Algorithm 1 with K=1                  (GT-GDA-like)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AlgorithmConfig
from repro.core import adversary as adversary_lib
from repro.core import compression as compression_lib
from repro.core import mixing as mixing_lib
from repro.core import packing
from repro.core import sparse_topology as sparse_lib
from repro.core import stochastic_topology as stoch_lib
from repro.core import topology as topo_lib
from repro.core.minimax import MinimaxProblem
from repro.kernels import ops as kernel_ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KGTState:
    x: Any          # (n, …) per-client primal variables
    y: Any          # (n, …) per-client dual variables
    cx: Any         # (n, …) gradient-tracking correction for x
    cy: Any         # (n, …) gradient-tracking correction for y
    round: jnp.ndarray  # scalar int32
    # Error-feedback residuals for compressed gossip (cfg.gossip_compress):
    # packed (n, D) f32 buffers in core.packing layout, one per variable.
    # None (an empty pytree node) when compression is off, so exact-gossip
    # states keep their historical leaf structure — old checkpoints restore
    # unchanged and the engine's template validation sees identical trees.
    ef_x: Any = None
    ef_y: Any = None
    # The problem's per-round counters (``MinimaxProblem.counters``) of the
    # round that made this state: {name: f32 scalar}; None without them.
    counters: Any = None


def _tree_axpy(a: float, x_tree, y_tree):
    """a * x + y elementwise over pytrees, f32 accumulate, keep y dtype."""
    return jax.tree.map(
        lambda x, y: (a * x.astype(jnp.float32) + y.astype(jnp.float32)).astype(y.dtype),
        x_tree, y_tree)


def _tree_sub(x_tree, y_tree):
    return jax.tree.map(lambda x, y: x - y, x_tree, y_tree)


def _tree_scale(a: float, tree):
    return jax.tree.map(lambda x: (a * x.astype(jnp.float32)).astype(x.dtype), tree)


def _replicate(tree, n: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)


def _client_broadcast(mask, ndim: int):
    """(n,) mask -> (n, 1, …, 1) for broadcasting against an (n, …) leaf."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _tree_mask_clients(mask, tree):
    """Zero the leaves of inactive clients (mask 0).  ×1.0 in f32 is exact,
    so active clients' values are bit-unchanged."""
    def one(x):
        m = _client_broadcast(mask.astype(jnp.float32), x.ndim)
        return (x.astype(jnp.float32) * m).astype(x.dtype)

    return jax.tree.map(one, tree)


def _freeze_inactive(mask, new_state: "KGTState", old_state: "KGTState"):
    """Per-client select: active clients take the round's result, inactive
    clients keep (θ, c) bit-exactly.  The masked Δ and self-loop W already
    make the inactive rows no-ops mathematically; the where pins them
    bit-exactly regardless of float summation order."""
    def pick(new, old):
        return jax.tree.map(
            lambda a, b: jnp.where(_client_broadcast(mask, a.ndim), a, b),
            new, old)

    return KGTState(
        x=pick(new_state.x, old_state.x),
        y=pick(new_state.y, old_state.y),
        cx=pick(new_state.cx, old_state.cx),
        cy=pick(new_state.cy, old_state.cy),
        round=new_state.round,
        # EF residuals freeze with the rest of the inactive client's state
        # (tree.map over None is a no-op for the uncompressed case)
        ef_x=pick(new_state.ef_x, old_state.ef_x),
        ef_y=pick(new_state.ef_y, old_state.ef_y))


def init_state(
    problem: MinimaxProblem,
    cfg: AlgorithmConfig,
    key,
    init_batch=None,
    init_keys=None,
) -> KGTState:
    """Shared x0/y0 across clients; corrections per the paper's initialization
    c_i = −∇F_i(x0,y0;ξ_i) + (1/n)Σ_j ∇F_j(x0,y0;ξ_j)  (Lemma 8 ⇒ Σ_i c_i = 0).
    For variants without tracking, corrections are zeros.
    """
    n = cfg.num_clients
    kx, ky, kg = jax.random.split(key, 3)
    x0 = problem.init_x(kx)
    y0 = problem.init_y(ky)
    x = _replicate(x0, n)
    y = _replicate(y0, n)

    track = cfg.algorithm in ("kgt_minimax", "gt_gda")
    if track and init_batch is not None:
        keys = init_keys if init_keys is not None else jax.random.split(kg, n)
        gx, gy = jax.vmap(problem.grads)(x, y, init_batch, keys)
        cx = jax.tree.map(lambda g: g.mean(0, keepdims=True) - g, gx)
        cy = jax.tree.map(lambda g: g.mean(0, keepdims=True) - g, gy)
    else:
        cx = jax.tree.map(jnp.zeros_like, x)
        cy = jax.tree.map(jnp.zeros_like, y)
    if cfg.correction_dtype != "float32":
        cd = jnp.dtype(cfg.correction_dtype)
        cx = jax.tree.map(lambda c: c.astype(cd), cx)
        cy = jax.tree.map(lambda c: c.astype(cd), cy)
    ef_x = ef_y = None
    if compression_lib.validate_method(cfg.gossip_compress) is not None:
        # zero EF residual per variable, packed (n, D) — round 0 transmits
        # Q(Δ) with nothing carried
        ef_x = compression_lib.init_ef(n, packing.pack_spec(x).dim)
        ef_y = compression_lib.init_ef(n, packing.pack_spec(y).dim)
    counters = None
    if problem.counters is not None:
        counters = {name: jnp.zeros((), jnp.float32)
                    for name in problem.counter_names}
    return KGTState(x=x, y=y, cx=cx, cy=cy, round=jnp.int32(0),
                    ef_x=ef_x, ef_y=ef_y, counters=counters)


def point_etas(cfg: AlgorithmConfig) -> dict:
    """The traced-stepsize bundle for ``make_round_step(traced_etas=True)``.

    ``corr_x``/``corr_y`` are the line-7/8 correction scales ±1/(K·η_c),
    precomputed **host-side in float64** — the same Python-float arithmetic
    the static path performs — so a trajectory run with traced etas is
    bit-identical to one compiled with the etas baked in (the in-graph f32
    division ``1/(K·η)`` can differ from the f64 value by an ulp).
    """
    k = 1 if cfg.algorithm in ("dsgda", "gt_gda") else cfg.local_steps
    return {
        "eta_cx": np.float32(cfg.eta_cx),
        "eta_cy": np.float32(cfg.eta_cy),
        "eta_sx": np.float32(cfg.eta_sx),
        "eta_sy": np.float32(cfg.eta_sy),
        "corr_x": np.float32(1.0 / (k * cfg.eta_cx)),
        "corr_y": np.float32(-1.0 / (k * cfg.eta_cy)),
    }


def make_round_step(
    problem: MinimaxProblem,
    cfg: AlgorithmConfig,
    w: Optional[np.ndarray] = None,
    lr_scale: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    *,
    traced_etas: bool = False,
    traced_w: bool = False,
    participation: bool = False,
    byzantine: bool = False,
    clients_sharded: bool = False,
):
    """Builds round_step(state, batches, keys) -> state.

    ``batches``: pytree with leading dims (K, n, …) — one per (local step,
    client).  ``keys``: (K, n) PRNG keys.  ``lr_scale``: optional schedule
    multiplier as a function of the round index.

    ``traced_etas=True`` changes the signature to
    ``round_step(state, batches, keys, etas)`` where ``etas`` is the scalar
    bundle of :func:`point_etas` carried as traced values — what lets
    ``repro.sweep`` vmap one compiled program over trajectories that differ
    only in their stepsizes.  The stepsizes in ``cfg`` are ignored on that
    path; compose any schedule into the eta values instead of ``lr_scale``.

    ``traced_w=True`` appends an ``(n, n)`` mixing matrix to the signature:
    W becomes a traced operand of the round — alongside the eta bundle on
    the sweep path — instead of a constant baked into the program, which is
    what lets a per-round *random* topology (``repro.core
    .stochastic_topology``) ride the engine's sampler slot.  ``participation
    =True`` appends an ``(n,)`` per-round client mask: inactive clients run
    no effective local update (their Δ is zeroed), drop every gossip link
    (self-loop fallback, :func:`stochastic_topology.masked_w` applied to
    whatever W the round uses), and their (θ, c) freeze bit-exactly; the
    Σ_i c_i = 0 tracking invariant holds under any mask because the masked
    W stays doubly stochastic.  ``byzantine=True`` appends a
    :class:`repro.core.adversary.Adversary` pytree: each attacker's
    *outgoing* Δ is corrupted right after the local steps — the attacked Δ
    rides every downstream use (gossip, its own correction, mixing), so
    under any doubly stochastic W the Σc = 0 identity survives every attack
    (an attacked Δ is still just a Δ); honest rows are bit-untouched.
    Extras order: ``round_step(state, batches, keys[, etas][, w][, mask]
    [, adversary])``.

    The **robust** ``mixing_impl``\\s (``mixing.ROBUST_IMPLS``:
    ``coord_median`` / ``trimmed_mean`` and their ``sparse_*``
    neighbor-gather forms) defend against those attacks by replacing every
    ``Σ_j w_ij v_j`` with a per-coordinate order statistic over the support
    of this round's W.  The aggregation R is nonlinear, so the parameter
    update becomes the one-pass ``θ ← R(θ + η_s Δ)`` (the linear split
    ``Wθ + η_s WΔ`` no longer exists) and the line-7/8 corrections keep
    their shape, ``c += ±(Δ − R(Δ))/(K η_c)``, but are **not** mean-
    preserving — Σ_i c_i drifts (boundedly, on the honest subset) instead
    of staying 0.  See docs/architecture.md § adversary axis.

    With ``mixing_impl="sparse_packed"`` the mixing matrix is a
    :class:`repro.core.sparse_topology.SparseTopology` everywhere a dense
    (n, n) array would appear: ``w`` may be a ``SparseTopology`` (a dense
    array is bridged via ``from_dense``; omitted, the support is built by
    ``sparse_mixing_matrix(cfg.topology, n)``), the ``traced_w`` extra is a
    ``SparseTopology`` pytree (see ``sparse_topology.make_sparse_w_sampler``),
    participation masking applies ``sparse_masked_w`` to the neighbor lists,
    and the round epilogue runs the neighbor-gather kernel
    (``kernels.ops.sparse_gossip_round``) — O(n·max_deg·D) per round with no
    (n, n) materialization anywhere.

    The round names its parts with ``jax.named_scope``, which only adds
    ``op_name`` metadata: ``kgt.grads`` (each local step's gradient oracle,
    forward and backward), ``kgt.local_update`` (``g + c`` and the SGDA
    step) and ``kgt.epilogue`` (Δ to the new state, every lowering but
    ``fused_round``, whose kernel runs under none).

    ``clients_sharded=True`` says the state's client axis is split across a
    mesh's devices: the dense gossip of a static or cycled W then lowers as
    ``mixing.mix_dense_sharded`` (collective-permutes over the client shifts
    a static ring-like W weighs, else one all-gather a leaf).  On the
    ``dense`` lowering the parameter gossip ``Wx, Wy`` is then formed from
    the round's starting state, and the local steps, which run unrolled,
    wait for it: its collectives run at the round's start, beside the
    sampler's draw, instead of after the local steps' loops.  On one
    device there is nothing to hide, and the early gossip would only keep a
    second copy of x live through the local steps, so that program is left
    as it was.
    """
    if traced_etas and lr_scale is not None:
        raise ValueError(
            "traced_etas carries per-trajectory stepsizes; fold the schedule "
            "into the eta values instead of passing lr_scale")
    if cfg.mixing_impl not in mixing_lib.MIXING_IMPLS:
        raise ValueError(
            f"unknown mixing_impl {cfg.mixing_impl!r}: {mixing_lib.MIXING_IMPLS}")
    if cfg.topology_cycle and cfg.mixing_impl.endswith("ring"):
        # the time-varying path lowers gossip densely per round; a
        # neighbor-only ring exchange cannot realize arbitrary cycle members
        raise ValueError(
            f"mixing_impl={cfg.mixing_impl!r} is not supported with "
            "topology_cycle; use 'dense', 'fused_dense', or 'pallas_packed'")
    if traced_w and cfg.topology_cycle:
        raise ValueError(
            "traced_w supplies W per round; topology_cycle would fight it — "
            "drop the cycle (sample the W sequence instead) or traced_w")
    sparse = cfg.mixing_impl == "sparse_packed"
    robust = cfg.mixing_impl in mixing_lib.ROBUST_IMPLS
    sparse_robust = robust and cfg.mixing_impl.startswith("sparse_")
    # sparse_w: W is a SparseTopology everywhere a dense array would appear
    sparse_w = sparse or sparse_robust
    robust_rule = mixing_lib.robust_rule(cfg.mixing_impl) if robust else None
    fused = cfg.mixing_impl == "fused_round"
    compress = compression_lib.validate_method(cfg.gossip_compress)
    if compress and cfg.mixing_impl not in ("pallas_packed", "fused_round"):
        raise ValueError(
            f"gossip_compress={cfg.gossip_compress!r} quantizes the packed "
            f"(n, D) round delta; mixing_impl={cfg.mixing_impl!r} has no "
            "packed buffer — use 'pallas_packed' or 'fused_round'")
    if fused:
        if problem.affine_coeffs is None:
            raise ValueError(
                "mixing_impl='fused_round' runs the K local steps as affine "
                "updates inside the kernel; this problem has no "
                "affine_coeffs oracle — use 'pallas_packed'")
        if byzantine:
            # the attack corrupts the per-leaf Δ tree, which never exists on
            # the whole-round path (Δ is born packed inside the kernel)
            raise ValueError(
                "mixing_impl='fused_round' does not support byzantine; "
                "use 'pallas_packed' (the attack applies pre-packing)")
    if cfg.topology_cycle and (sparse_w or robust):
        # the cycle path stacks dense (n, n) members and lowers them through
        # mix_dense per round; neither the neighbor-list representation nor
        # the robust order-statistic epilogue rides it
        raise ValueError(
            f"mixing_impl={cfg.mixing_impl!r} is not supported with "
            "topology_cycle; use traced_w with a per-round sampler instead")
    dynamic_w = traced_w or participation
    packed = cfg.mixing_impl == "pallas_packed"
    pack_gd = (None if cfg.gossip_dtype in (None, "float32")
               else jnp.dtype(cfg.gossip_dtype))
    dense_mix = (mixing_lib.mix_dense_sharded if clients_sharded
                 else mixing_lib.mix_dense)
    early_gossip = clients_sharded and cfg.mixing_impl == "dense"
    if dynamic_w and not packed and not sparse and not robust and not fused:
        # validates the impl (ring-style neighbor exchanges cannot realize a
        # per-round arbitrary W) and gives us mix(tree, w) with w traced
        traced_mix = mixing_lib.make_traced_mixer(
            cfg.mixing_impl, cfg.gossip_dtype)
    if cfg.topology_cycle:
        # time-varying gossip: W selected per round from the cycle
        ws = jnp.stack([
            jnp.asarray(topo_lib.mixing_matrix(t, cfg.num_clients), jnp.float32)
            for t in cfg.topology_cycle])
        gd = pack_gd
        get_w = lambda round_idx: ws[round_idx % len(cfg.topology_cycle)]

        def make_mix(round_idx):
            w_t = get_w(round_idx)
            return lambda tree: dense_mix(tree, w_t, gossip_dtype=gd)
    else:
        if w is None and not traced_w:
            w = (sparse_lib.sparse_mixing_matrix(cfg.topology, cfg.num_clients)
                 if sparse_w
                 else topo_lib.mixing_matrix(cfg.topology, cfg.num_clients))
        if sparse_w:
            w_arr = (None if w is None
                     else (w if isinstance(w, sparse_lib.SparseTopology)
                           else sparse_lib.from_dense(np.asarray(w))))
        else:
            w_arr = None if w is None else jnp.asarray(w, jnp.float32)
        get_w = lambda round_idx: w_arr
        if packed or sparse_w or robust or dynamic_w or fused:
            make_mix = None  # W is consumed directly, per round
        else:
            static_mix = mixing_lib.make_mixer(
                cfg.topology, cfg.mixing_impl, w, cfg.gossip_dtype,
                clients_sharded=clients_sharded)
            make_mix = lambda round_idx: static_mix
    gossip_backend = kernel_ops.resolve_gossip_backend(cfg.gossip_backend)
    algo = cfg.algorithm
    track = algo in ("kgt_minimax", "gt_gda")
    k_steps = 1 if algo in ("dsgda", "gt_gda") else cfg.local_steps
    grads_v = jax.vmap(problem.grads_with_stats)
    # (K, n)-batched affine-coefficient oracle for the whole-round kernel
    coeffs_v = (jax.vmap(jax.vmap(problem.affine_coeffs)) if fused else None)

    def _fused_round(state: KGTState, batches, keys, w_t, mask,
                     eta_cx, eta_cy, eta_sx, eta_sy, corr_x, corr_y):
        """Whole-round lowering: one kernel call runs the K affine local
        steps AND the gossip epilogue over the packed z = (x; y) state —
        see kernels/fused_round.py.  Requires G constant across the K local
        steps (the quadratic workload: per-client coefficients ride the
        batch unchanged per step, only the noise shift h varies)."""
        spec_x = packing.pack_spec(state.x)
        spec_y = packing.pack_spec(state.y)
        n, dzx, dzy = spec_x.n, spec_x.dim, spec_y.dim
        dz = dzx + dzy
        bat = jax.tree.map(lambda b: b[:k_steps], batches)
        kk = jax.tree.map(lambda b: b[:k_steps], keys)
        g_all, h_all = coeffs_v(bat, kk)          # (K, n, dz, dz), (K, n, dz)
        g_mat = g_all[0]   # G is step-constant; XLA DCEs the dead steps

        def cat(xb, yb):
            return jnp.concatenate([xb, yb], axis=1)

        z0 = cat(packing.pack(state.x, spec_x), packing.pack(state.y, spec_y))
        if track:
            cb = cat(packing.pack(state.cx), packing.pack(state.cy))
        else:
            cb = jnp.zeros((n, dz), jnp.float32)
        if compress:
            if state.ef_x is None:
                raise ValueError(
                    "gossip_compress is set but the state carries no EF "
                    "residual — build it with init_state under the same cfg")
            efb = cat(state.ef_x, state.ef_y)
        else:
            efb = jnp.zeros((n, dz), jnp.float32)
        # per-column vectors: x-block descends, y-block ascends; corr = 0
        # encodes the no-tracking variants (c' = c exactly)
        one_x = jnp.ones((dzx,), jnp.float32)
        one_y = jnp.ones((dzy,), jnp.float32)
        base_step = jnp.concatenate([eta_cx * one_x, -eta_cy * one_y])
        mask_col = (jnp.ones((n, 1), jnp.float32) if mask is None
                    else mask.astype(jnp.float32)[:, None])
        step = mask_col * base_step[None, :]       # inactive ⇒ Δ ≡ 0 exactly
        etas = jnp.broadcast_to(
            jnp.concatenate([eta_sx * one_x, eta_sy * one_y])[None, :],
            (n, dz))
        if track:
            corr = jnp.concatenate([corr_x * one_x, corr_y * one_y])
        else:
            corr = jnp.zeros((dz,), jnp.float32)
        corr = jnp.broadcast_to(corr[None, :], (n, dz))
        mask_full = jnp.broadcast_to(mask_col, (n, dz))
        z_new, c_new, ef_new = kernel_ops.fused_round(
            w_t, z0, cb, efb, g_mat, h_all, step, etas, corr, mask_full,
            backend=gossip_backend, compress=compress,
            gossip_dtype=cfg.gossip_dtype)
        if track:
            cx = packing.unpack(c_new[:, :dzx], packing.pack_spec(state.cx))
            cy = packing.unpack(c_new[:, dzx:], packing.pack_spec(state.cy))
        else:
            cx, cy = state.cx, state.cy
        new_state = KGTState(
            x=packing.unpack(z_new[:, :dzx], spec_x),
            y=packing.unpack(z_new[:, dzx:], spec_y),
            cx=cx, cy=cy, round=state.round + 1,
            ef_x=ef_new[:, :dzx] if compress else state.ef_x,
            ef_y=ef_new[:, dzx:] if compress else state.ef_y)
        return (new_state if mask is None
                else _freeze_inactive(mask, new_state, state))

    def _epilogue(state: KGTState, xk, yk, w_t, mix, mask, adv,
                  eta_sx, eta_sy, corr_x, corr_y, mixed=None) -> KGTState:
        """Algorithm 1, lines 6-11, for every lowering but the whole-round
        kernel: Δ = x^K − x from the local steps' end point, then the
        correction update and the parameter mixing, then the freeze of
        inactive clients."""
        dx = _tree_sub(xk, state.x)   # Δx = x^{(t)+K} − x^{(t)}
        dy = _tree_sub(yk, state.y)
        if adv is not None:
            # Byzantine corruption of the outgoing Δ: the attacked value
            # rides every use below — gossip, the attacker's own correction,
            # mixing — so the attacker "follows the protocol" with its
            # corrupted update and honest rows stay bit-untouched.  Applied
            # before the participation zeroing so an inactive attacker
            # contributes nothing, exactly like an inactive honest client.
            dx = adversary_lib.apply_attack(adv, dx, stream=0)
            dy = adversary_lib.apply_attack(adv, dy, stream=1)
        if mask is not None:
            # inactive clients contribute no local update: with Δ_i = 0 and
            # W row/col i = e_i (masked_w above), lines 7-11 are no-ops for
            # them and their mass never reaches active clients
            dx = _tree_mask_clients(mask, dx)
            dy = _tree_mask_clients(mask, dy)

        new_state = _mix_round(state, dx, dy, w_t, mix, mask,
                               eta_sx, eta_sy, corr_x, corr_y, mixed)
        return (new_state if mask is None
                else _freeze_inactive(mask, new_state, state))

    def _mix_round(state: KGTState, dx, dy, w_t, mix, mask,
                   eta_sx, eta_sy, corr_x, corr_y, mixed=None) -> KGTState:
        """Lines 7-11 from this round's (attacked, masked) Δ, per lowering;
        inactive clients are frozen by the caller.  ``mixed``: the
        parameter gossip ``(Wx, Wy)`` when the round formed it early."""
        if robust:
            # Robust-aggregation epilogue: R replaces every W contraction.
            # R is nonlinear, so the parameter update is the one-pass
            # θ ← R(θ + η_s Δ) (aggregating the stepped parameters — the
            # linear split Wθ + η_s·WΔ does not exist), and the corrections
            # keep line 7/8's shape c += ±(Δ − R(Δ))/(K η_c) without the
            # Σc = 0 telescoping (R is not doubly stochastic).  W enters
            # only as the support of each client's neighbor set, so
            # participation masking above composes: a masked client's
            # support collapses to {self} and _freeze_inactive pins it.
            def agg(buf):
                if sparse_robust:
                    return mixing_lib.robust_mix_sparse(
                        buf, w_t, rule=robust_rule, trim=cfg.robust_trim,
                        gossip_dtype=pack_gd)
                return mixing_lib.robust_mix_dense(
                    buf, w_t, rule=robust_rule, trim=cfg.robust_trim,
                    gossip_dtype=pack_gd)

            spec_x = packing.pack_spec(state.x)
            spec_y = packing.pack_spec(state.y)
            dxb = packing.pack(dx, spec_x)
            dyb = packing.pack(dy, spec_y)
            xb = agg(packing.pack(state.x, spec_x) + eta_sx * dxb)
            yb = agg(packing.pack(state.y, spec_y) + eta_sy * dyb)
            if track:
                spec_cx = packing.pack_spec(state.cx)
                spec_cy = packing.pack_spec(state.cy)
                cx0 = packing.pack(state.cx, spec_cx)
                cy0 = packing.pack(state.cy, spec_cy)
                cxb = (cx0.astype(jnp.float32)
                       + corr_x * (dxb - agg(dxb))).astype(cx0.dtype)
                cyb = (cy0.astype(jnp.float32)
                       + corr_y * (dyb - agg(dyb))).astype(cy0.dtype)
                cx = packing.unpack(cxb, spec_cx)
                cy = packing.unpack(cyb, spec_cy)
            else:
                cx, cy = state.cx, state.cy
            return KGTState(
                x=packing.unpack(xb, spec_x), y=packing.unpack(yb, spec_y),
                cx=cx, cy=cy, round=state.round + 1)

        if sparse:
            # Sparse whole-state lowering: same fused epilogue as the packed
            # branch below, but W is padded-CSR neighbor lists and the
            # contraction is a neighbor-row gather — O(n·max_deg·D), no
            # (n, n) array at any point.  See repro.kernels.neighbor_gossip.
            spec_x = packing.pack_spec(state.x)
            spec_y = packing.pack_spec(state.y)
            if not track:
                xb = sparse_lib.sparse_mix(
                    w_t, packing.pack(state.x, spec_x)
                    + eta_sx * packing.pack(dx, spec_x), gossip_dtype=pack_gd)
                yb = sparse_lib.sparse_mix(
                    w_t, packing.pack(state.y, spec_y)
                    + eta_sy * packing.pack(dy, spec_y), gossip_dtype=pack_gd)
                return KGTState(
                    x=packing.unpack(xb, spec_x), y=packing.unpack(yb, spec_y),
                    cx=state.cx, cy=state.cy, round=state.round + 1)
            spec_cx = packing.pack_spec(state.cx)
            spec_cy = packing.pack_spec(state.cy)
            xb, cxb = kernel_ops.sparse_gossip_round(
                w_t.neighbor_idx, w_t.neighbor_w, w_t.self_w,
                packing.pack(dx, spec_x), packing.pack(state.x, spec_x),
                packing.pack(state.cx, spec_cx), eta_sx, corr_x,
                backend=gossip_backend, gossip_dtype=cfg.gossip_dtype)
            yb, cyb = kernel_ops.sparse_gossip_round(
                w_t.neighbor_idx, w_t.neighbor_w, w_t.self_w,
                packing.pack(dy, spec_y), packing.pack(state.y, spec_y),
                packing.pack(state.cy, spec_cy), eta_sy, corr_y,
                backend=gossip_backend, gossip_dtype=cfg.gossip_dtype)
            return KGTState(
                x=packing.unpack(xb, spec_x),
                y=packing.unpack(yb, spec_y),
                cx=packing.unpack(cxb, spec_cx),
                cy=packing.unpack(cyb, spec_cy),
                round=state.round + 1)

        if packed:
            # Whole-state lowering: ravel each variable into one (n, D)
            # buffer and run the entire round epilogue (lines 7-11) as one
            # fused pass — θ_new = Wθ + η_s·WΔ and c += ±(Δ − WΔ)/(K·η_c)
            # computed together, one collective per variable instead of one
            # (or two) per leaf.  See repro.kernels.{gossip,ops}.
            spec_x = packing.pack_spec(state.x)
            spec_y = packing.pack_spec(state.y)
            dxb = packing.pack(dx, spec_x)
            dyb = packing.pack(dy, spec_y)
            if compress:
                # EF quantization of the *transmitted* Δ: the same q rides
                # the mixing and the correction below, which preserves the
                # Σc = 0 telescoping (see core.compression).  The residual
                # is per-variable KGTState EF state.
                if state.ef_x is None:
                    raise ValueError(
                        "gossip_compress is set but the state carries no EF "
                        "residual — build it with init_state under the same "
                        "cfg")
                dxb, efx = compression_lib.ef_transmit(
                    dxb, state.ef_x, compress, mask)
                dyb, efy = compression_lib.ef_transmit(
                    dyb, state.ef_y, compress, mask)
            else:
                efx, efy = state.ef_x, state.ef_y
            if not track:
                # no correction state: the epilogue degenerates to a single
                # gossip of the already-stepped parameters, W(θ + η_s·Δ) —
                # don't move (n, D) correction buffers through the kernel
                # just to multiply them by zero
                xb = dense_mix(packing.pack(state.x, spec_x) + eta_sx * dxb,
                               w_t, gossip_dtype=pack_gd)
                yb = dense_mix(packing.pack(state.y, spec_y) + eta_sy * dyb,
                               w_t, gossip_dtype=pack_gd)
                return KGTState(
                    x=packing.unpack(xb, spec_x), y=packing.unpack(yb, spec_y),
                    cx=state.cx, cy=state.cy, round=state.round + 1,
                    ef_x=efx, ef_y=efy)
            spec_cx = packing.pack_spec(state.cx)
            spec_cy = packing.pack_spec(state.cy)
            # pack() builds fresh buffers each round, so their storage can
            # back the kernel outputs (donation is a no-op under jit/CPU —
            # see kernels.ops.fused_gossip_round)
            xb, cxb = kernel_ops.fused_gossip_round(
                w_t, dxb, packing.pack(state.x, spec_x),
                packing.pack(state.cx, spec_cx), eta_sx, corr_x,
                backend=gossip_backend, gossip_dtype=cfg.gossip_dtype,
                donate=True)
            yb, cyb = kernel_ops.fused_gossip_round(
                w_t, dyb, packing.pack(state.y, spec_y),
                packing.pack(state.cy, spec_cy), eta_sy, corr_y,
                backend=gossip_backend, gossip_dtype=cfg.gossip_dtype,
                donate=True)
            return KGTState(
                x=packing.unpack(xb, spec_x),
                y=packing.unpack(yb, spec_y),
                cx=packing.unpack(cxb, spec_cx),
                cy=packing.unpack(cyb, spec_cy),
                round=state.round + 1,
                ef_x=efx, ef_y=efy)

        # Algorithm 1 communicates two quantities per variable per round:
        # Δ (lines 7-8) and the parameters (lines 10-11).  The faithful
        # implementation issues two gossips; the "fused_*" variants PACK both
        # into one collective per leaf (same bytes, half the collective
        # launches — beyond-paper, bit-identical).
        if cfg.mixing_impl.startswith("fused"):
            def pack_mix(delta, base):
                pairs = jax.tree.map(
                    lambda d, b: jnp.stack([d.astype(jnp.float32),
                                            b.astype(jnp.float32)], axis=1),
                    delta, base)
                mixed = mix(pairs)
                md = jax.tree.map(lambda p: p[:, 0], mixed)
                mb = jax.tree.map(lambda p: p[:, 1], mixed)
                return md, mb

            mdx, mx = pack_mix(dx, state.x)
            mdy, my = pack_mix(dy, state.y)
        else:
            mdx, mdy = mix(dx), mix(dy)
            mx, my = mixed if mixed is not None else (mix(state.x),
                                                      mix(state.y))

        if track:
            # c^x += (Δx − WΔx)/(K η_cx) ;  c^y −= (Δy − WΔy)/(K η_cy)
            cx = _tree_axpy(corr_x, _tree_sub(dx, mdx), state.cx)
            cy = _tree_axpy(corr_y, _tree_sub(dy, mdy), state.cy)
        else:
            cx, cy = state.cx, state.cy

        # x ← W(x + η_s Δx) = Wx + η_s·WΔx   (second gossip: the parameters)
        x_new = _tree_axpy(eta_sx, mdx, mx)
        y_new = _tree_axpy(eta_sy, mdy, my)

        return KGTState(x=x_new, y=y_new, cx=cx, cy=cy,
                        round=state.round + 1)

    def _round(state: KGTState, batches, keys,
               eta_cx, eta_cy, eta_sx, eta_sy, corr_x, corr_y,
               w_t=None, mask=None, adv=None) -> KGTState:
        if packed or sparse_w or robust or dynamic_w or fused:
            if w_t is None:
                w_t = get_w(state.round)
            if mask is not None:
                w_t = (sparse_lib.sparse_masked_w(w_t, mask) if sparse_w
                       else stoch_lib.masked_w(w_t, mask))
            mix = (None if packed or sparse_w or robust or fused
                   else (lambda tree: traced_mix(tree, w_t)))
        else:
            mix = make_mix(state.round)

        if fused:
            # the local steps live inside the kernel — skip the scan below
            return _fused_round(state, batches, keys, w_t, mask,
                                eta_cx, eta_cy, eta_sx, eta_sy,
                                corr_x, corr_y)

        def local_step(carry, inp):
            xx, yy = carry
            batch_k, key_k = inp
            with jax.named_scope("kgt.grads"):
                (gx, gy), stats = grads_v(xx, yy, batch_k, key_k)
            with jax.named_scope("kgt.local_update"):
                if track:   # g + c
                    gx = _tree_axpy(1.0, state.cx, gx)
                    gy = _tree_axpy(1.0, state.cy, gy)
                xx = _tree_axpy(-eta_cx, gx, xx)
                yy = _tree_axpy(eta_cy, gy, yy)
            return (xx, yy), stats

        x0, y0, mixed = state.x, state.y, None
        if early_gossip:
            with jax.named_scope("kgt.epilogue"):
                mixed = (mix(state.x), mix(state.y))
            # The scheduler keeps no collective in flight across the layer
            # loops of the local steps, so Wx left free would run after
            # them.  Making the local steps wait for it starts its collectives
            # at the round's start, under the sampler's draw.
            mixed, x0, y0 = jax.lax.optimization_barrier((mixed, x0, y0))
        # slice exactly k_steps from the provided K-stacked batch
        bat = jax.tree.map(lambda b: b[:k_steps], batches)
        kk = jax.tree.map(lambda b: b[:k_steps], keys)
        (xk, yk), stats = jax.lax.scan(
            local_step, (x0, y0), (bat, kk),
            unroll=k_steps if early_gossip else 1)
        with jax.named_scope("kgt.epilogue"):
            new_state = _epilogue(state, xk, yk, w_t, mix, mask, adv,
                                  eta_sx, eta_sy, corr_x, corr_y, mixed)
        if state.counters is not None:   # this round's counters
            new_state = dataclasses.replace(
                new_state, counters=problem.counters(stats))
        return new_state

    n_extras = int(traced_w) + int(participation) + int(byzantine)
    extras_doc = "".join(
        f"[{name}]" for name, on in (("w", traced_w), ("mask", participation),
                                     ("adversary", byzantine))
        if on)

    def _split_extras(extras):
        if len(extras) != n_extras:
            raise TypeError(
                f"round_step expected {n_extras} extra operand(s) "
                f"{extras_doc or '(none)'} after keys"
                f"{' and etas' if traced_etas else ''}, got {len(extras)}")
        it = iter(extras)
        w_t = next(it) if traced_w else None
        mask = next(it) if participation else None
        adv = next(it) if byzantine else None
        return w_t, mask, adv

    if traced_etas:
        def round_step(state: KGTState, batches, keys, etas,
                       *extras) -> KGTState:
            w_t, mask, adv = _split_extras(extras)
            # η_s = 1 for the no-tracking baselines (plain parameter
            # averaging), exactly like the static path below
            esx = etas["eta_sx"] if track else 1.0
            esy = etas["eta_sy"] if track else 1.0
            return _round(state, batches, keys, etas["eta_cx"], etas["eta_cy"],
                          esx, esy,
                          etas["corr_x"] if track else None,
                          etas["corr_y"] if track else None,
                          w_t=w_t, mask=mask, adv=adv)

        return round_step

    # Communication stepsizes (η_s = 1 for the no-tracking baselines: plain
    # parameter averaging x ← W(x + Δx)).
    eta_sx = cfg.eta_sx if track else 1.0
    eta_sy = cfg.eta_sy if track else 1.0

    def round_step(state: KGTState, batches, keys, *extras) -> KGTState:
        w_t, mask, adv = _split_extras(extras)
        scale = lr_scale(state.round) if lr_scale is not None else 1.0
        eta_cx = cfg.eta_cx * scale
        eta_cy = cfg.eta_cy * scale
        corr_x = 1.0 / (k_steps * eta_cx) if track else None
        corr_y = -1.0 / (k_steps * eta_cy) if track else None
        return _round(state, batches, keys, eta_cx, eta_cy, eta_sx, eta_sy,
                      corr_x, corr_y, w_t=w_t, mask=mask, adv=adv)

    return round_step


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

def mean_over_clients(tree):
    return jax.tree.map(lambda x: x.mean(0), tree)


def correction_mean_norm(tree) -> jnp.ndarray:
    """‖c̄‖ = ‖(1/n) Σ_i c_i‖ over all leaves — Lemma 8 says exactly 0 for
    the tracking variants; drift here means the correction update is wrong."""
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(l.mean(0).astype(jnp.float32)))
        for l in jax.tree.leaves(tree)))


def diagnostics(problem: MinimaxProblem, state: KGTState):
    """Exact ‖∇Φ(x̄)‖ (quadratic problems) + consensus errors."""
    out = {
        "consensus_x": mixing_lib.consensus_error(state.x),
        "consensus_y": mixing_lib.consensus_error(state.y),
        # the x-correction norm keeps its historical key; cy is the mirrored
        # line-8 state and deserves the same Lemma-8 watchdog
        "correction_mean_norm": correction_mean_norm(state.cx),
        "correction_mean_norm_y": correction_mean_norm(state.cy),
    }
    if problem.phi_grad is not None:
        xbar = mean_over_clients(state.x)
        out["phi_grad_norm"] = problem.phi_grad_norm(xbar)
    return out
