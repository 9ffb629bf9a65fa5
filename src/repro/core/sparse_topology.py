"""Sparse communication topologies: padded-CSR neighbor lists, never (n, n).

Every dense gossip path — ``mixing.mix_dense``, the packed Pallas kernel,
``stochastic_topology``'s samplers — materializes the full (n, n) mixing
matrix, so per-round memory and compute are O(n²) and the clients axis caps
out at toy sizes.  The K-GT-Minimax analysis (Assumption 4) only needs a
symmetric doubly stochastic W *supported on the communication graph*; for
the ring/torus/exp graphs the paper sweeps, that support is O(n) or
O(n log n) edges.  This module is the edge-proportional representation:

:class:`SparseTopology` — per-client neighbor lists in padded CSR form:

* ``neighbor_idx (n, max_deg) int32`` — neighbor ids, ascending per row;
  padding slots repeat the client's own index;
* ``neighbor_w (n, max_deg) f32`` — the off-diagonal weights w_ij; padding
  slots carry weight 0.0, so every consumer can reduce over all slots;
* ``self_w (n,) f32`` — the diagonal w_ii;
* ``degree (n,) int32`` — valid slots per row (``offsets`` derives the
  flattened-CSR segment offsets).

It is a registered pytree, so a *sampled* per-round topology flows as a
traced operand through jit/scan/vmap exactly like the dense W did on the
churn path — at O(n·max_deg) instead of O(n²).

Constructors mirror ``repro.core.topology`` (``sparse_ring`` /
``sparse_torus`` / ``sparse_exp`` / ``sparse_full`` / ``sparse_star`` via
Metropolis–Hastings weights, which coincide with the dense constructors'
weights on all of these graphs), plus :func:`sparse_hierarchical` — a
cluster-of-clusters graph (dense intra-cluster, ring over cluster leaders)
for the federated "silos of devices" regime.  :func:`from_dense` /
:func:`densify` bridge to the dense world bit-exactly (round-trip tested).

Sampling (the sparse analogue of ``repro.core.stochastic_topology``) emits
**edge lists, never an (n, n) array**: :func:`make_sparse_w_sampler` draws
per-round Erdős–Rényi percolation of the support graph, randomized pairwise
gossip on a support edge, or per-client dropout — each on the same
``round_stream_key``/W_STREAM fold_in discipline as the dense samplers, so
checkpoint restore regenerates the identical sequence.  Every draw is
symmetric doubly stochastic by construction, so the Σ_i c_i = 0 and
mean-dynamics invariants carry over at any scale.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stochastic_topology as stoch_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseTopology:
    """Padded-CSR neighbor-list mixing matrix (see module docstring)."""
    neighbor_idx: jnp.ndarray   # (n, max_deg) int32, padding = own index
    neighbor_w: jnp.ndarray     # (n, max_deg) f32,   padding = 0.0
    self_w: jnp.ndarray         # (n,) f32 diagonal
    degree: jnp.ndarray         # (n,) int32 valid slots per row

    @property
    def n(self) -> int:
        return self.neighbor_idx.shape[0]

    @property
    def max_degree(self) -> int:
        return self.neighbor_idx.shape[1]

    @property
    def offsets(self) -> jnp.ndarray:
        """(n+1,) segment offsets of the flattened (ragged) CSR view."""
        return jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(self.degree.astype(jnp.int32))])

    @property
    def num_edges(self) -> int:
        """Directed edge count Σ_i deg_i (host; needs a concrete degree)."""
        return int(np.sum(np.asarray(self.degree)))


# ---------------------------------------------------------------------------
# dense bridge
# ---------------------------------------------------------------------------

def from_dense(w, tol: float = 0.0) -> SparseTopology:
    """Extract the neighbor lists of a dense (n, n) mixing matrix.

    Off-diagonal entries with ``|w_ij| > tol`` become neighbor slots in
    ascending column order; the diagonal becomes ``self_w``.  Weights are
    stored f32, so ``densify(from_dense(w))`` equals ``w.astype(f32)``
    bit-for-bit.  This is the O(n²) bridge for matrices that already exist —
    use the direct ``sparse_*`` constructors to *build* at scale.
    """
    w = np.asarray(w)
    n = w.shape[0]
    if w.shape != (n, n):
        raise ValueError(f"from_dense needs a square matrix, got {w.shape}")
    cols_per = []
    deg = np.zeros(n, np.int32)
    for i in range(n):
        cols = [j for j in range(n) if j != i and abs(w[i, j]) > tol]
        cols_per.append(cols)
        deg[i] = len(cols)
    max_deg = max(1, int(deg.max()) if n else 1)
    nidx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, max_deg))
    nw = np.zeros((n, max_deg), np.float32)
    for i, cols in enumerate(cols_per):
        if cols:
            nidx[i, : len(cols)] = np.asarray(cols, np.int32)
            nw[i, : len(cols)] = w[i, cols].astype(np.float32)
    return SparseTopology(
        neighbor_idx=jnp.asarray(nidx), neighbor_w=jnp.asarray(nw),
        self_w=jnp.asarray(np.diag(w).astype(np.float32)),
        degree=jnp.asarray(deg))


def densify(sp: SparseTopology) -> jnp.ndarray:
    """(n, n) f32 mixing matrix of ``sp`` (traceable).

    Padding slots scatter-add exact 0.0 onto the diagonal, so the round
    trip ``densify(from_dense(w))`` reproduces ``w.astype(f32)`` bit-exactly.
    """
    n = sp.n
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], sp.neighbor_idx.shape)
    w = jnp.zeros((n, n), jnp.float32)
    w = w.at[rows, sp.neighbor_idx].add(sp.neighbor_w.astype(jnp.float32))
    return w.at[jnp.arange(n), jnp.arange(n)].add(sp.self_w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# direct constructors (O(edges), host-side)
# ---------------------------------------------------------------------------

def _from_adjacency(adj) -> SparseTopology:
    """Metropolis–Hastings weights on symmetric adjacency lists:
    w_ij = 1/(1 + max(d_i, d_j)), each diagonal takes its row's leftover.

    On ring/torus/exp/full/star this reproduces the dense constructors'
    weights (for the uniform-degree hand-weighted graphs MH degenerates to
    the same 1/3, 1/5, 1/n values).
    """
    n = len(adj)
    deg = np.array([len(a) for a in adj], np.int32)
    max_deg = max(1, int(deg.max()) if n else 1)
    nidx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, max_deg))
    nw = np.zeros((n, max_deg), np.float32)
    sw = np.zeros((n,), np.float32)
    for i in range(n):
        nbrs = sorted(adj[i])
        if nbrs:
            row = np.array([1.0 / (1 + max(int(deg[i]), int(deg[j])))
                            for j in nbrs], np.float64)
            nidx[i, : len(nbrs)] = np.asarray(nbrs, np.int32)
            nw[i, : len(nbrs)] = row.astype(np.float32)
            sw[i] = np.float32(1.0 - row.sum())
        else:
            sw[i] = np.float32(1.0)
    return SparseTopology(
        neighbor_idx=jnp.asarray(nidx), neighbor_w=jnp.asarray(nw),
        self_w=jnp.asarray(sw), degree=jnp.asarray(deg))


def sparse_ring(n: int) -> SparseTopology:
    adj = [set() for _ in range(n)]
    if n > 1:
        for i in range(n):
            adj[i].update({(i + 1) % n, (i - 1) % n})
    return _from_adjacency(adj)


def sparse_torus(n: int) -> SparseTopology:
    s = int(round(np.sqrt(n)))
    if s * s != n:
        raise ValueError(f"torus needs a square n, got {n}")
    if s <= 2:
        return sparse_ring(n)
    adj = [set() for _ in range(n)]
    for r in range(s):
        for c in range(s):
            i = r * s + c
            for dr, dc in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                adj[i].add(((r + dr) % s) * s + (c + dc) % s)
    return _from_adjacency(adj)


def sparse_exp(n: int) -> SparseTopology:
    """Exponential graph (i ↔ i ± 2^k): degree O(log n), the scaling
    workhorse — spectral gap independent of n at ~2 log₂ n edges/client."""
    adj = [set() for _ in range(n)]
    k = 1
    while k < n:
        for i in range(n):
            adj[i].update({(i + k) % n, (i - k) % n})
        k *= 2
    for i in range(n):
        adj[i].discard(i)
    return _from_adjacency(adj)


def sparse_full(n: int) -> SparseTopology:
    stoch_lib.check_dense_materialization(n, "sparse_full (complete graph)")
    adj = [set(range(n)) - {i} for i in range(n)]
    return _from_adjacency(adj)


def sparse_star(n: int) -> SparseTopology:
    stoch_lib.check_dense_materialization(n, "sparse_star (hub degree n-1)")
    adj = [set() for _ in range(n)]
    for i in range(1, n):
        adj[0].add(i)
        adj[i].add(0)
    return _from_adjacency(adj)


def sparse_hierarchical(n: int, cluster_size: int) -> SparseTopology:
    """Cluster-of-clusters graph: each cluster of ``cluster_size`` clients is
    fully connected internally; cluster leaders (the first member) form a
    ring across clusters.  Max degree is cluster_size + 1 regardless of n —
    the federated "silos of devices" topology.  MH weights keep it symmetric
    doubly stochastic despite the leader/member degree asymmetry."""
    if cluster_size < 1 or n % cluster_size != 0:
        raise ValueError(
            f"cluster_size must divide n, got n={n}, cluster_size={cluster_size}")
    q = n // cluster_size
    adj = [set() for _ in range(n)]
    for g in range(q):
        base = g * cluster_size
        for a in range(base, base + cluster_size):
            for b in range(base, base + cluster_size):
                if a != b:
                    adj[a].add(b)
    if q == 2:
        adj[0].add(cluster_size)
        adj[cluster_size].add(0)
    elif q > 2:
        for g in range(q):
            lead, nxt = g * cluster_size, ((g + 1) % q) * cluster_size
            adj[lead].add(nxt)
            adj[nxt].add(lead)
    return _from_adjacency(adj)


SPARSE_TOPOLOGIES = {
    "ring": sparse_ring,
    "torus": sparse_torus,
    "exp": sparse_exp,
    "full": sparse_full,
    "star": sparse_star,
}


def sparse_mixing_matrix(name: str, n: int) -> SparseTopology:
    """Sparse counterpart of ``topology.mixing_matrix(name, n)``."""
    try:
        return SPARSE_TOPOLOGIES[name](n)
    except KeyError:
        raise KeyError(
            f"unknown topology {name!r}: {sorted(SPARSE_TOPOLOGIES)}") from None


# ---------------------------------------------------------------------------
# traceable per-round operators
# ---------------------------------------------------------------------------

def sparse_masked_w(sp: SparseTopology, mask) -> SparseTopology:
    """Self-loop fallback on the neighbor lists — the sparse analogue of
    ``stochastic_topology.masked_w``: w′_ij = w_ij·m_i·m_j on edges, each
    diagonal absorbs its row's lost mass.  Symmetric doubly stochastic for
    any 0/1 mask; a masked-out client's row collapses to e_i exactly
    (self_w = 1.0, all neighbor weights 0.0)."""
    m = mask.astype(jnp.float32)
    nw = (sp.neighbor_w.astype(jnp.float32)
          * m[:, None] * m[sp.neighbor_idx])
    return dataclasses.replace(
        sp, neighbor_w=nw, self_w=1.0 - nw.sum(1))


def sparse_mix(sp: SparseTopology, buf, gossip_dtype=None) -> jnp.ndarray:
    """``(W @ buf)`` for a packed (n, D) buffer by neighbor-row gather —
    O(n·max_deg·D) instead of the dense O(n²·D) contraction.  Mirrors
    ``mixing.mix_dense``'s dtype rules: operands (the communicated values
    and weights) narrow to ``gossip_dtype``, accumulation is f32."""
    out_dtype = buf.dtype
    bg = buf.astype(gossip_dtype) if gossip_dtype is not None else buf
    nwg = sp.neighbor_w.astype(bg.dtype)
    swg = sp.self_w.astype(bg.dtype)
    gathered = jnp.take(bg, sp.neighbor_idx, axis=0)      # (n, max_deg, D)
    mixed = (swg.astype(jnp.float32)[:, None] * bg.astype(jnp.float32)
             + jnp.einsum("nm,nmd->nd", nwg, gathered,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST))
    return mixed.astype(out_dtype)


# ---------------------------------------------------------------------------
# per-round samplers (edge lists, never an (n, n) array)
# ---------------------------------------------------------------------------

def _pair_slots(nidx: np.ndarray, deg: np.ndarray) -> np.ndarray:
    """pair_slot[i, s] = the slot of i in neighbor j's list, where
    j = nidx[i, s] — the inverse map that lets a per-edge draw be read
    canonically from both endpoints.  Padding slots point at themselves."""
    n, m = nidx.shape
    ps = np.tile(np.arange(m, dtype=np.int32), (n, 1))
    slot_of = [
        {int(j): s for s, j in enumerate(nidx[i, : int(deg[i])])}
        for i in range(n)
    ]
    for i in range(n):
        for s in range(int(deg[i])):
            j = int(nidx[i, s])
            if i not in slot_of[j]:
                raise ValueError(
                    f"support graph is not symmetric: edge {i}->{j} has no "
                    f"reverse slot")
            ps[i, s] = slot_of[j][i]
    return ps


def make_sparse_w_sampler(
    family: str,
    support: SparseTopology,
    key,
    *,
    edge_prob=0.5,
    client_drop_prob=0.3,
) -> Callable[[jnp.ndarray], SparseTopology]:
    """``w_fn(round_idx) -> SparseTopology``: this round's sparse mixing
    matrix, drawn on the support graph — the edge-list analogue of
    ``stochastic_topology.make_w_sampler``.

    * ``static`` — the support itself every round;
    * ``erdos_renyi`` — each support edge kept independently with
      probability ``edge_prob`` (bond percolation of the support; one
      canonical uniform per undirected edge keeps the draw symmetric),
      Metropolis–Hastings weights on the realized degrees;
    * ``pairwise`` — randomized gossip on one uniformly random *support*
      edge (the dense family draws from all pairs; with a sparse support
      only graph edges can communicate);
    * ``dropout`` — per-client Bernoulli link dropout of the support
      weights with self-loop fallback (same draws as the dense family).

    Pure and jit-traceable in ``round_idx`` on the
    ``round_stream_key``/W_STREAM discipline; ``edge_prob`` /
    ``client_drop_prob`` may be traced scalars (sweep axes).  The support
    must be host-concrete (its structure is precomputed here once).
    """
    if family not in stoch_lib.TOPOLOGY_FAMILIES:
        raise ValueError(
            f"unknown topology family {family!r}: {stoch_lib.TOPOLOGY_FAMILIES}")
    if family == "static":
        return lambda round_idx: support

    nidx = np.asarray(support.neighbor_idx)
    deg = np.asarray(support.degree)
    n, m = nidx.shape
    if family == "dropout":
        def sample_dropout(r):
            keep = stoch_lib.bernoulli_mask(
                stoch_lib.round_stream_key(key, r, stoch_lib.W_STREAM),
                n, 1.0 - client_drop_prob)
            return sparse_masked_w(support, keep)

        return sample_dropout

    pair_slot = jnp.asarray(_pair_slots(nidx, deg))
    valid = jnp.asarray(nidx != np.arange(n, dtype=np.int32)[:, None])
    nidx_j = support.neighbor_idx

    if family == "erdos_renyi":
        own = jnp.arange(n, dtype=nidx_j.dtype)[:, None]

        def sample_er(r):
            u = jax.random.uniform(
                stoch_lib.round_stream_key(key, r, stoch_lib.W_STREAM), (n, m))
            # one canonical uniform per undirected edge: the draw "belongs"
            # to the lower-indexed endpoint; the higher endpoint gathers it
            # through the pair_slot inverse map, so keep is symmetric
            u_canon = jnp.where(nidx_j < own, u[nidx_j, pair_slot], u)
            keep = valid & (u_canon < edge_prob)
            d = keep.sum(1)
            denom = 1.0 + jnp.maximum(d[:, None], d[nidx_j]).astype(jnp.float32)
            nw = keep.astype(jnp.float32) / denom
            return SparseTopology(
                neighbor_idx=nidx_j, neighbor_w=nw,
                self_w=1.0 - nw.sum(1), degree=support.degree)

        return sample_er

    # pairwise: one uniformly random support edge averages, everyone holds.
    # Host-precompute the directed i<j edge list once; the per-round draw is
    # a single randint + two scatter writes.
    ei, es = np.nonzero((nidx > np.arange(n)[:, None])
                        & (np.arange(m)[None, :] < deg[:, None]))
    num_edges = len(ei)
    if num_edges == 0:
        identity = SparseTopology(
            neighbor_idx=nidx_j,
            neighbor_w=jnp.zeros((n, m), jnp.float32),
            self_w=jnp.ones((n,), jnp.float32), degree=support.degree)
        return lambda round_idx: identity
    edges_i = jnp.asarray(ei.astype(np.int32))
    edges_s = jnp.asarray(es.astype(np.int32))

    def sample_pairwise(r):
        t = jax.random.randint(
            stoch_lib.round_stream_key(key, r, stoch_lib.W_STREAM),
            (), 0, num_edges)
        i, s = edges_i[t], edges_s[t]
        j, s2 = nidx_j[i, s], pair_slot[i, s]
        nw = jnp.zeros((n, m), jnp.float32).at[i, s].set(0.5).at[j, s2].set(0.5)
        sw = jnp.ones((n,), jnp.float32).at[i].set(0.5).at[j].set(0.5)
        return SparseTopology(neighbor_idx=nidx_j, neighbor_w=nw,
                              self_w=sw, degree=support.degree)

    return sample_pairwise
