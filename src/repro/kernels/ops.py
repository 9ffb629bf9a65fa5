"""Jit'd dispatch wrappers around the Pallas kernels.

Callers use model-layout tensors ((B, S, H, D) attention, (B, S, H, P) SSD);
these wrappers handle layout, GQA folding, block padding and the
pallas/interpret/xla backend choice, which every caller names: ``"pallas"``
compiles the kernel for the TPU, ``"interpret"`` runs the same kernel in
the Pallas interpreter (validation on CPU), and ``"xla"`` routes to the
pure-jnp oracle (what the dry-run lowers).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import fused_round as fround_lib
from repro.kernels import gossip as gossip_lib
from repro.kernels import neighbor_gossip as ngossip_lib
from repro.kernels import ref as ref_lib
from repro.kernels import rglru_scan as rg
from repro.kernels import ssd_scan as ssd


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@partial(jax.jit, static_argnames=("causal", "window", "backend", "block_q", "block_k"))
def flash_attention(q, k, v, *, backend: str, causal: bool = True,
                    window: int = 0, block_q: int = 128,
                    block_k: int = 128):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    if backend == "xla":
        of = ref_lib.attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        qp, _ = _pad_to(qf, 1, block_q)
        kp, _ = _pad_to(kf, 1, block_k)
        vp, _ = _pad_to(vf, 1, block_k)
        of = fa.flash_attention_bhsd(
            qp, kp, vp, causal=causal, window=window, block_q=block_q,
            block_k=block_k, interpret=(backend == "interpret"))
        of = of[:, :sq]
    return of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan(xdt, loga, bm, cm, *, backend: str, chunk: int = 64):
    """xdt: (B, S, H, P); loga: (B, S, H); bm, cm: (B, S, N)."""
    b, s, h, p = xdt.shape
    xf = xdt.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    lf = loga.transpose(0, 2, 1).reshape(b * h, s)
    if backend == "xla":
        yf = ref_lib.ssd_ref(xf, lf, bm, cm)
    else:
        xf2, _ = _pad_to(xf, 1, chunk)
        lf2, _ = _pad_to(lf, 1, chunk)
        bm2, _ = _pad_to(bm, 1, chunk)
        cm2, _ = _pad_to(cm, 1, chunk)
        yf = ssd.ssd_scan_bh(xf2, lf2, bm2, cm2, chunk=chunk,
                             interpret=(backend == "interpret"))[:, :s]
    return yf.reshape(b, h, s, p).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_t", "block_v", "backend"))
def fused_cross_entropy(hidden, weight, labels, *, backend: str,
                        block_t: int = 128, block_v: int = 512):
    """Per-token NLL without materializing (N, V) logits.
    hidden: (N, d); weight: (V, d); labels: (N,) int32."""
    if backend == "xla":
        return ref_lib.fused_ce_ref(hidden, weight, labels)
    from repro.kernels import cross_entropy as ce

    return ce.fused_ce_nd(hidden, weight, labels, block_t=block_t,
                          block_v=block_v, interpret=(backend == "interpret"))


GOSSIP_BACKENDS = ("auto", "pallas", "interpret", "xla")


def resolve_gossip_backend(backend: str) -> str:
    """"auto" -> the Pallas kernel on TPU, the packed-xla oracle elsewhere
    (interpret mode is for validation, far too slow for training loops; the
    xla oracle still gets the packed single-collective lowering on a mesh)."""
    if backend not in GOSSIP_BACKENDS:
        raise ValueError(f"unknown gossip_backend {backend!r}: {GOSSIP_BACKENDS}")
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# Measured-best block_d per packed shape, recorded by bench_gossip's one-time
# autotune sweep ({128, 256, 512, 1024} per (n, D)).  Resolution happens in
# the *unjitted* dispatchers below — block_d is a static argument, so it must
# be a concrete int before tracing.  Unmeasured shapes fall back to the old
# hardcoded-512 heuristic (clamped to the padded D).
_BLOCK_D_CACHE: dict = {}
BLOCK_D_CANDIDATES = (128, 256, 512, 1024)


def record_block_d(n: int, d: int, block_d: int) -> None:
    _BLOCK_D_CACHE[(int(n), int(d))] = int(block_d)


def best_block_d(n: int, d: int):
    """The measured winner for (n, D), or None if never autotuned."""
    return _BLOCK_D_CACHE.get((int(n), int(d)))


def _resolve_block_d(n: int, d: int, block_d) -> int:
    if block_d is None:
        block_d = _BLOCK_D_CACHE.get((n, d), 512)
    return min(block_d, max(128, -(-d // 128) * 128))


def _fused_gossip_body(w, delta, theta, c, eta_s, corr_scale, *,
                       backend: str, block_d: int, gossip_dtype):
    gd = (None if gossip_dtype in (None, "float32")
          else jnp.dtype(gossip_dtype))
    eta_s = jnp.float32(eta_s)
    corr_scale = jnp.float32(corr_scale)
    if backend == "xla":
        return ref_lib.fused_gossip_ref(w, delta, theta, c, eta_s,
                                        corr_scale, gossip_dtype=gd)
    n, d = delta.shape
    w = jnp.asarray(w, jnp.float32)
    wp, _ = _pad_to(w, 0, 8)
    wp, _ = _pad_to(wp, 1, 8)
    blk = block_d
    aligned = n % 8 == 0 and d % blk == 0

    def prep(x):
        x = x.astype(jnp.float32)
        if aligned:
            return x
        x, _ = _pad_to(x, 0, 8)
        x, _ = _pad_to(x, 1, blk)
        return x

    scalars = jnp.stack([eta_s, corr_scale])
    theta_new, c_new = gossip_lib.fused_gossip_nd(
        wp, prep(delta), prep(theta), prep(c), scalars, block_d=blk,
        gossip_dtype=gd, interpret=(backend == "interpret"))
    if aligned:
        return theta_new, c_new
    return theta_new[:n, :d], c_new[:n, :d]


_STATIC_GOSSIP = ("backend", "block_d", "gossip_dtype")
_fused_gossip_jit = jax.jit(_fused_gossip_body, static_argnames=_STATIC_GOSSIP)
# Donating variant: delta/theta/c are consumed (the packed round step builds
# fresh buffers each round, so their storage can back the outputs).  W is NOT
# donated — callers reuse it across the x- and y-variable calls of one round.
_fused_gossip_jit_donate = jax.jit(
    _fused_gossip_body, static_argnames=_STATIC_GOSSIP,
    donate_argnums=(1, 2, 3))


def fused_gossip_round(w, delta, theta, c, eta_s, corr_scale, *,
                       backend: str, block_d=None,
                       gossip_dtype=None, donate: bool = False):
    """Fused round epilogue over packed client state.

    w: (n, n); delta/theta/c: (n, D).  Returns f32
    (θ_new, c_new) = (Wθ + η_s·WΔ, c + corr_scale·(Δ − WΔ)).

    ``gossip_dtype`` (None/str) narrows the matmul operands only.  The
    pallas/interpret path pads n to the f32 sublane multiple (8) and D to
    the block multiple with zeros — zero-padded W rows/cols contribute
    nothing — and slices back to (n, D); both copies are skipped when the
    shape is already aligned.  ``block_d=None`` uses the autotuned winner
    for this (n, D) if bench_gossip has recorded one, else 512.
    ``donate=True`` lets XLA reuse delta/theta/c storage for the outputs —
    only pass it when the caller holds the last reference to those buffers.
    Donation is honored only for concrete (non-traced) inputs on a backend
    that supports aliasing (TPU/GPU); under an outer jit the enclosing
    computation owns the buffers, and on CPU jax ignores donation with a
    "donated buffers were not usable" warning — both cases route to the
    plain variant so callers can pass donate=True unconditionally.
    """
    blk = _resolve_block_d(delta.shape[0], delta.shape[1], block_d)
    use_donate = (donate and not isinstance(delta, jax.core.Tracer)
                  and jax.default_backend() in ("tpu", "gpu"))
    fn = _fused_gossip_jit_donate if use_donate else _fused_gossip_jit
    return fn(w, delta, theta, c, eta_s, corr_scale, backend=backend,
              block_d=blk, gossip_dtype=gossip_dtype)


#: Mosaic's default scoped-VMEM limit on TPU v5e.  ``fused_round`` is
#: grid-less, so every operand and temporary must fit in it at once.
SCOPED_VMEM_BYTES = 16 * 2**20


def fused_round_vmem_bytes(n_pad: int, dz_pad: int, k_steps: int) -> int:
    """Scoped VMEM the compiled ``fused_round`` kernel needs (f32 words):
    G once (n·dz²); the lane-padded right-hand side of the batched G·z
    matvec (256 lanes per client row); the K offset rows of h, 7 input, 3
    output and 3 temporary ``(n, dz)`` blocks; and W's lane-padded tile.
    Fitted to what the v5e compiler reports (tests/test_tpu_compile.py
    holds the guard to the compiler at n=8 on both sides of the bound)."""
    return 4 * (n_pad * dz_pad * dz_pad
                + (256 + k_steps + 13) * n_pad * dz_pad
                + n_pad * max(128, n_pad))


@partial(jax.jit, static_argnames=("backend", "compress", "gossip_dtype"))
def fused_round(w, z0, c, ef, g_mat, h_steps, step, etas, corr, mask, *,
                backend: str, compress=None, gossip_dtype=None):
    """Whole Algorithm-1 round (K affine local SGDA steps + gossip epilogue)
    in one kernel pass over the packed z = (x; y) state.

    w: (n, n); z0/c/ef: (n, dz); g_mat: (n, dz, dz); h_steps: (K, n, dz);
    step/etas/corr/mask: (n, dz) broadcast per-column vectors (signs and
    masks pre-folded by the caller — see kernels/fused_round.py for the
    exact semantics).  Returns f32 (z_new, c_new, ef_new).

    ``compress`` (None / "bf16" / "int8") turns on error-feedback quantized
    gossip; ``ef`` is the carried residual (pass zeros when None — it flows
    through untouched).  The pallas/interpret path pads n → 8 and dz → 128
    with zeros (padded G rows/cols and masked rows contribute nothing) and
    slices back; ``backend="xla"`` routes to ``ref.fused_round_ref``.  It
    raises where the padded problem would not fit the scoped VMEM
    (:func:`fused_round_vmem_bytes`).
    """
    gd = (None if gossip_dtype in (None, "float32")
          else jnp.dtype(gossip_dtype))
    if backend == "xla":
        return ref_lib.fused_round_ref(
            w, z0, c, ef, g_mat, h_steps, step, etas, corr, mask,
            compress=compress, gossip_dtype=gd)
    n, dz = z0.shape
    k_steps = h_steps.shape[0]
    n_pad = -(-n // 8) * 8
    dz_pad = max(128, -(-dz // 128) * 128)
    need = fused_round_vmem_bytes(n_pad, dz_pad, k_steps)
    if need > SCOPED_VMEM_BYTES:
        raise ValueError(
            f"fused_round holds the whole round in VMEM: n={n_pad}, "
            f"dz={dz_pad}, K={k_steps} needs {need / 2**20:.1f} MiB of the "
            f"{SCOPED_VMEM_BYTES / 2**20:.0f} MiB scoped limit — use "
            f"mixing_impl='pallas_packed' for larger problems")
    wp, _ = _pad_to(jnp.asarray(w, jnp.float32), 0, 8)
    wp, _ = _pad_to(wp, 1, 8)

    def prep(x):
        x, _ = _pad_to(x.astype(jnp.float32), 0, 8)
        x, _ = _pad_to(x, 1, 128)
        return x

    gp, _ = _pad_to(g_mat.astype(jnp.float32), 0, 8)
    gp, _ = _pad_to(gp, 1, 128)
    gp, _ = _pad_to(gp, 2, 128)
    hp, _ = _pad_to(h_steps.astype(jnp.float32), 1, 8)
    hp, _ = _pad_to(hp, 2, 128)
    z_new, c_new, e_new = fround_lib.fused_round_nd(
        wp, prep(z0), prep(c), prep(ef), gp, hp, prep(step), prep(etas),
        prep(corr), prep(mask), k_steps=k_steps, compress=compress,
        gossip_dtype=gd, interpret=(backend == "interpret"))
    return z_new[:n, :dz], c_new[:n, :dz], e_new[:n, :dz]


#: Client rows per program of the neighbor-gather kernel.
SPARSE_BLOCK_N = 256


def sparse_block_d(n_pad: int, d: int, block_d: int) -> int:
    """The D tile of the neighbor-gather kernel: ``block_d`` clamped to the
    padded D and to what fits the scoped VMEM, where the two gather sources
    (Δ and θ over the whole client axis, double-buffered) dominate —
    16·n·BD bytes.  Raises where even one 128-lane tile does not fit."""
    fit = SCOPED_VMEM_BYTES * 3 // 4 // (16 * n_pad) // 128 * 128
    if fit < 128:
        raise ValueError(
            f"sparse_gossip: n={n_pad} clients do not fit one 128-lane "
            f"gather tile in {SCOPED_VMEM_BYTES / 2**20:.0f} MiB of VMEM")
    return min(block_d, fit, max(128, -(-d // 128) * 128))


@partial(jax.jit, static_argnames=("backend", "block_d", "gossip_dtype"))
def sparse_gossip_round(neighbor_idx, neighbor_w, self_w, delta, theta, c,
                        eta_s, corr_scale, *, backend: str,
                        block_d: int = 512, gossip_dtype=None):
    """Fused round epilogue over packed client state, sparse W.

    neighbor_idx: (n, max_deg) int32 padded-CSR neighbor lists (padding =
    own index); neighbor_w: (n, max_deg) with padding weight 0; self_w:
    (n,) diagonal; delta/theta/c: (n, D).  Returns f32
    (θ_new, c_new) = (Wθ + η_s·WΔ, c + corr_scale·(Δ − WΔ)) — the same
    contract as ``fused_gossip_round`` at O(n·max_deg·D) instead of
    O(n²·D).  Raw arrays, not a ``SparseTopology``: callers unpack the
    pytree so the kernels package stays free of core imports.

    The pallas/interpret path prepends the augmented self slot (slot 0 =
    own row at weight w_ii), narrows the weights to ``gossip_dtype``, pads
    n to the client-block multiple (padded rows gather row 0 at weight 0.0
    — contribute nothing) and D to the block multiple, and slices back to
    (n, D).
    """
    gd = (None if gossip_dtype in (None, "float32")
          else jnp.dtype(gossip_dtype))
    eta_s = jnp.float32(eta_s)
    corr_scale = jnp.float32(corr_scale)
    if backend == "xla":
        return ref_lib.sparse_gossip_ref(
            neighbor_idx, neighbor_w, self_w, delta, theta, c, eta_s,
            corr_scale, gossip_dtype=gd)
    n, d = delta.shape
    own = jnp.arange(n, dtype=jnp.int32)[:, None]
    aidx = jnp.concatenate([own, neighbor_idx.astype(jnp.int32)], axis=1)
    aw = jnp.concatenate([self_w.astype(jnp.float32)[:, None],
                          neighbor_w.astype(jnp.float32)], axis=1)
    aw = (aw if gd is None else aw.astype(gd)).astype(jnp.float32)
    block_n = min(SPARSE_BLOCK_N, -(-n // 8) * 8)
    aidx, _ = _pad_to(aidx, 0, block_n)
    aw, _ = _pad_to(aw, 0, block_n)
    blk = sparse_block_d(aidx.shape[0], d, block_d)

    def prep(x):
        x, _ = _pad_to(x.astype(jnp.float32), 0, block_n)
        x, _ = _pad_to(x, 1, blk)
        return x

    scalars = jnp.stack([eta_s, corr_scale])
    theta_new, c_new = ngossip_lib.sparse_gossip_nd(
        aidx, aw, prep(delta), prep(theta), prep(c), scalars, block_d=blk,
        block_n=block_n, gossip_dtype=gd, interpret=(backend == "interpret"))
    return theta_new[:n, :d], c_new[:n, :d]


@partial(jax.jit, static_argnames=("chunk", "backend"))
def rglru_scan(a, u, *, backend: str, chunk: int = 256):
    """a, u: (B, S, W) -> h: (B, S, W)."""
    if backend == "xla":
        return ref_lib.rglru_ref(a, u)
    s = a.shape[1]
    a2, _ = _pad_to(a, 1, chunk)
    u2, _ = _pad_to(u, 1, chunk)
    # padded a=0 keeps the carry exact for the real rows
    return rg.rglru_scan_b(a2, u2, chunk=chunk,
                           interpret=(backend == "interpret"))[:, :s]
