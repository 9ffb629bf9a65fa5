"""Gossip mixing operators over pytrees with a leading clients dim.

The per-leaf gossip ``Σ_j w_ij T_j`` is ``dense``: the full (n, n) mixing
matrix W, faithful to the paper (arbitrary topology), whose lowering
follows from W and from where the clients live.  Up to ``UNROLL_MAX_CLIENTS``
clients on one device it is a weighted sum of client slices, above that an
einsum.  Across a mesh's clients axis (``mix_dense_sharded``) a static W
that weighs only some client shifts (a ring, a torus) is a weighted sum of
the rolled tensor, one collective-permute per weighed shift, and any other
W the einsum, whose contraction over the sharded clients dim lowers to an
all-gather of the full tensor, (n-1)·|T| bytes in per client.
``mix_packed`` and ``mix_sparse`` ravel the whole tree into one (n, D)
buffer first; the latter gathers neighbor rows instead of forming W.

``gossip_dtype`` optionally downcasts the *communicated* values (beyond-paper
optimization; tracking state stays f32).

Beyond the linear lowerings, the **robust** impls (:data:`ROBUST_IMPLS`)
replace ``Σ_j w_ij T_j`` with a per-coordinate order statistic over each
client's neighbor set — coordinate-wise median or b-trimmed mean over
``{j : w_ij > 0} ∪ {self}`` — the Byzantine-tolerant aggregation of
robust decentralized learning (Ghiasvand et al., PAPERS.md).  They consume
W only as a *support* (which neighbors count), are **nonlinear** (so not
doubly stochastic: Σ_i R(T)_i ≠ Σ_i T_i in general), and compose with
participation masking for free — ``masked_w`` collapses an inactive row's
support to ``{self}``, so the client keeps its own value exactly.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core import sparse_topology as sparse_lib


def _cast(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree.map(lambda x: x.astype(dtype), tree)


# Largest client count whose dense gossip is an unrolled sum of n² terms.
UNROLL_MAX_CLIENTS = 8


def _dense_one(x, w, gossip_dtype, unroll: bool):
    orig = x.dtype
    xc = x.astype(gossip_dtype) if gossip_dtype is not None else x
    wc = w.astype(xc.dtype)
    n = x.shape[0]
    if unroll and n <= UNROLL_MAX_CLIENTS:
        # one f32 rounding per product and per sum, as in an f32 dot (and
        # a bf16 gossip's products are exact): HIGHEST's precision below
        col = (n,) + (1,) * (x.ndim - 1)
        mixed = wc[:, 0].astype(jnp.float32).reshape(col) * xc[0].astype(
            jnp.float32)
        for j in range(1, n):
            mixed = mixed + (wc[:, j].astype(jnp.float32).reshape(col)
                             * xc[j].astype(jnp.float32))
        return mixed.astype(orig)
    # einsum in the gossip dtype (keeps the all-gathered operand narrow),
    # accumulate in f32.  HIGHEST: at its default precision a TPU rounds f32
    # operands to bf16, which would round every parameter to bf16 each round
    # and erase the local steps' smaller updates.
    mixed = jnp.einsum(
        "ij,j...->i...", wc, xc,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return mixed.astype(orig)


def mix_dense(tree: Any, w, gossip_dtype=None) -> Any:
    """tree leaves: (n, ...) -> W @ leaves.

    Up to ``UNROLL_MAX_CLIENTS`` clients, ``W @ x`` is the weighted sum of
    client slices ``Σ_j W[:, j] ⊗ x[j]``, elementwise along the leaves' own
    axes: a contraction over the short client axis makes the TPU's layout
    assignment move that axis into the tiled minor dims, so the state the
    round loop carries would change layout twice a round.  Above it the n²
    unrolled terms would not pay, and the gossip is one einsum.
    """
    w = jnp.asarray(w, jnp.float32)
    return jax.tree.map(lambda x: _dense_one(x, w, gossip_dtype, True), tree)


def _client_shifts(w) -> dict:
    """{s: W[i, (i − s) mod n] over rows i} for each client shift s that
    some row weighs, or {} for a W known only when traced."""
    if isinstance(w, jax.core.Tracer):
        return {}
    w = np.asarray(w)
    rows = np.arange(w.shape[0])
    diags = {s: w[rows, (rows - s) % w.shape[0]] for s in range(w.shape[0])}
    return {s: d for s, d in diags.items() if np.any(d != 0)}


def _shifted_one(x, shifts: dict, gossip_dtype):
    orig = x.dtype
    xc = x.astype(gossip_dtype) if gossip_dtype is not None else x
    col = (x.shape[0],) + (1,) * (x.ndim - 1)
    mixed = None
    for s, d in sorted(shifts.items()):
        # roll(x, s)[i] = x[(i - s) mod n]: one collective-permute
        term = jnp.asarray(d, jnp.float32).reshape(col) * (
            xc if s == 0 else jnp.roll(xc, s, axis=0)).astype(jnp.float32)
        mixed = term if mixed is None else mixed + term
    return mixed.astype(orig)


def mix_dense_sharded(tree: Any, w, gossip_dtype=None) -> Any:
    """``mix_dense`` for leaves whose client axis is split across devices.

    A W whose rows weigh only some client shifts (a ring, a torus: a
    static W with a zero circulant diagonal) is the sum over those shifts
    of the rolled leaf, one collective-permute per shift but the self one,
    which moves (shifts − 1)·|T| bytes into each client.  Any other W is
    one einsum, whose contraction lowers to one all-gather per leaf,
    (n − 1)·|T| bytes in, where slicing the sharded axis would take n − 1
    permutes."""
    shifts = _client_shifts(w)
    if 0 < len(shifts) < np.shape(w)[0]:
        return jax.tree.map(lambda x: _shifted_one(x, shifts, gossip_dtype),
                            tree)
    w = jnp.asarray(w, jnp.float32)
    return jax.tree.map(lambda x: _dense_one(x, w, gossip_dtype, False), tree)


def mix_packed(tree: Any, w, gossip_dtype=None) -> Any:
    """One gossip for the whole pytree: ravel to (n, D), mix, unravel.

    Same math as ``mix_dense`` per leaf, but a single contraction over the
    packed buffer — one collective for the entire state instead of one per
    leaf.  The round-step path goes further (repro.kernels.ops
    ``fused_gossip_round`` fuses the correction/mixing epilogue too); this
    tree-level form serves generic callers.
    """
    spec = packing.pack_spec(tree)
    mixed = mix_dense(packing.pack(tree, spec), w, gossip_dtype=gossip_dtype)
    return packing.unpack(mixed, spec)


def mix_sparse(tree: Any, sp, gossip_dtype=None) -> Any:
    """One neighbor-gather gossip for the whole pytree: ravel to (n, D),
    ``sparse_topology.sparse_mix`` against the padded-CSR neighbor lists,
    unravel.  Same math as ``mix_packed`` at O(n·max_deg·D) instead of
    O(n²·D) — W never exists as an (n, n) array."""
    spec = packing.pack_spec(tree)
    mixed = sparse_lib.sparse_mix(sp, packing.pack(tree, spec),
                                  gossip_dtype=gossip_dtype)
    return packing.unpack(mixed, spec)


# ---------------------------------------------------------------------------
# robust (Byzantine-tolerant) aggregation
# ---------------------------------------------------------------------------

ROBUST_RULES = ("coord_median", "trimmed_mean")
# first-class mixing_impl names: dense form + sparse neighbor-gather form
ROBUST_IMPLS = ("coord_median", "trimmed_mean",
                "sparse_coord_median", "sparse_trimmed_mean")


def robust_rule(impl: str) -> str:
    """The aggregation rule of a robust mixing_impl name."""
    rule = impl[len("sparse_"):] if impl.startswith("sparse_") else impl
    if rule not in ROBUST_RULES:
        raise ValueError(f"not a robust mixing_impl: {impl!r} ({ROBUST_IMPLS})")
    return rule


def _robust_reduce(vals, valid, rule: str, trim: int) -> jnp.ndarray:
    """Per-coordinate order statistic over the valid slots of each row.

    vals: (n, m, D) candidate values per client; valid: (n, m) bool —
    invalid slots (padding, masked links, absent edges) are ignored, and so
    are non-finite values per coordinate: a client whose state has blown up
    (a diverged Byzantine attacker) must not occupy a trim slot forever —
    that would turn the symmetric b-trim into a permanently asymmetric trim
    of the honest values, a systematic bias.  Every row should keep ≥ 1
    finite valid slot per coordinate (the aggregating client itself).

    * ``coord_median`` — midpoint of the two middle order statistics of the
      k valid values (the even/odd-agnostic median).
    * ``trimmed_mean`` — mean after dropping the b smallest and b largest
      values per coordinate, b = min(trim, (k−1)//2) so at least one value
      always survives (the trim adapts to masked-down neighbor sets).

    k (hence b) is per-(row, coordinate): finiteness varies by coordinate.
    """
    if rule not in ROBUST_RULES:
        raise ValueError(f"unknown robust rule {rule!r}: {ROBUST_RULES}")
    vals = vals.astype(jnp.float32)
    n, m, d = vals.shape
    ok = valid[:, :, None] & jnp.isfinite(vals)              # (n, m, D)
    k = ok.sum(1).astype(jnp.int32)                          # (n, D) ≥ 1
    filled = jnp.where(ok, vals, jnp.inf)
    srt = jnp.sort(filled, axis=1)       # valid ascending, padding (inf) last
    if rule == "coord_median":
        lo = jnp.take_along_axis(srt, ((k - 1) // 2)[:, None, :], axis=1)
        hi = jnp.take_along_axis(srt, (k // 2)[:, None, :], axis=1)
        return (0.5 * (lo + hi))[:, 0, :]
    b = jnp.minimum(jnp.int32(trim), (k - 1) // 2)           # (n, D)
    rank = jnp.arange(m, dtype=jnp.int32)[None, :, None]
    keep = (rank >= b[:, None, :]) & (rank < (k - b)[:, None, :])
    # where-then-sum (not multiply) so the inf padding never meets a 0
    total = jnp.sum(jnp.where(keep, srt, 0.0), axis=1)
    return total / (k - 2 * b).astype(jnp.float32)


def robust_mix_dense(buf, w, *, rule: str, trim: int = 1,
                     gossip_dtype=None) -> jnp.ndarray:
    """Robust aggregation of a packed (n, D) buffer over the support of a
    dense (n, n) W: client i reduces over ``{j : w_ij > 0} ∪ {i}``.

    Mirrors ``mix_dense``'s dtype rules: the communicated values narrow to
    ``gossip_dtype``, the reduction itself runs in f32.
    """
    out_dtype = buf.dtype
    w = jnp.asarray(w, jnp.float32)
    n = w.shape[0]
    bg = (buf.astype(gossip_dtype) if gossip_dtype is not None
          else buf).astype(jnp.float32)
    valid = (w > 0.0) | jnp.eye(n, dtype=bool)
    vals = jnp.broadcast_to(bg[None, :, :], (n, n, bg.shape[1]))
    return _robust_reduce(vals, valid, rule, trim).astype(out_dtype)


def robust_mix_sparse(buf, sp, *, rule: str, trim: int = 1,
                      gossip_dtype=None) -> jnp.ndarray:
    """Neighbor-gather form of :func:`robust_mix_dense`: the candidate set
    is gathered through the padded-CSR neighbor lists — O(n·max_deg·D), no
    (n, n) array.  Validity comes from ``neighbor_w > 0``, so padding slots
    and masked links (``sparse_masked_w``) drop out and the self slot is
    always in; on ``densify``-equal supports this matches the dense form.
    """
    out_dtype = buf.dtype
    bg = (buf.astype(gossip_dtype) if gossip_dtype is not None
          else buf).astype(jnp.float32)
    n = sp.neighbor_idx.shape[0]
    gathered = jnp.take(bg, sp.neighbor_idx, axis=0)         # (n, max_deg, D)
    vals = jnp.concatenate([bg[:, None, :], gathered], axis=1)
    valid = jnp.concatenate(
        [jnp.ones((n, 1), bool), sp.neighbor_w > 0.0], axis=1)
    return _robust_reduce(vals, valid, rule, trim).astype(out_dtype)


def robust_mix_packed(tree: Any, w, *, rule: str, trim: int = 1,
                      gossip_dtype=None) -> Any:
    """Tree-level robust aggregation: ravel to (n, D), reduce, unravel.
    ``w`` dispatches the form — a ``SparseTopology`` takes the neighbor-
    gather path, anything array-like the dense one."""
    spec = packing.pack_spec(tree)
    red = (robust_mix_sparse if isinstance(w, sparse_lib.SparseTopology)
           else robust_mix_dense)
    mixed = red(packing.pack(tree, spec), w, rule=rule, trim=trim,
                gossip_dtype=gossip_dtype)
    return packing.unpack(mixed, spec)


MIXING_IMPLS = ("dense", "pallas_packed", "sparse_packed",
                "fused_round") + ROBUST_IMPLS


def make_mixer(impl: str, gossip_dtype: str = "float32", *, trim: int = 1,
               clients_sharded: bool = False):
    """Returns ``mix(tree, w) -> tree`` for the configured implementation.

    ``w`` is this round's W, whatever made it: a constant array for a
    static topology, a cycle's member or a per-round sampled or
    participation-masked matrix traced in the surrounding jit, or, for the
    ``sparse_*`` forms, a :class:`sparse_topology.SparseTopology`.  The
    lowering follows from W and from ``clients_sharded`` (the leaves'
    client axis is split across devices: the dense gossip then lowers as
    :func:`mix_dense_sharded`)."""
    if impl not in MIXING_IMPLS:
        raise ValueError(f"unknown mixing_impl {impl!r}: {MIXING_IMPLS}")
    gd = None if gossip_dtype in (None, "float32") else jnp.dtype(gossip_dtype)
    if impl in ROBUST_IMPLS:
        # W is a support here: robust_mix_packed dispatches on its form
        rule = robust_rule(impl)
        return lambda tree, w: robust_mix_packed(tree, w, rule=rule,
                                                 trim=trim, gossip_dtype=gd)
    if impl == "sparse_packed":
        return lambda tree, sp: mix_sparse(tree, sp, gossip_dtype=gd)
    if impl == "pallas_packed":
        return lambda tree, w: mix_packed(tree, w, gossip_dtype=gd)
    if impl == "fused_round":
        # whole-round lowering: there is no standalone mix step — the local
        # steps, gossip, and correction all live inside one kernel call,
        # routed by kgt_minimax.make_round_step.  Falling through to
        # mix_dense here would silently run the wrong program.
        raise ValueError(
            "mixing_impl='fused_round' has no standalone mixer; it is "
            "routed whole-round by kgt_minimax.make_round_step")
    # looked up when called, so that a replacement of either takes effect
    return lambda tree, w: (mix_dense_sharded if clients_sharded
                            else mix_dense)(tree, w, gossip_dtype=gd)


def consensus_error(tree: Any) -> jnp.ndarray:
    """(1/n) Σ_i ||T_i - mean_j T_j||² summed over leaves (client variance Ξ)."""
    def one(x):
        m = x.mean(0, keepdims=True)
        return jnp.sum(jnp.square((x - m).astype(jnp.float32))) / x.shape[0]
    return sum(jax.tree.leaves(jax.tree.map(one, tree)))
