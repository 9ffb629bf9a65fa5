"""Round-lowering microbenchmark: epilogue lowerings + the whole-round kernel.

Two workloads, one claim — "the Pallas path is the fastest way to run a
round on this host":

* **Round rows** — the timed comparison (``wall_ms``, one workload so the
  rows are comparable): the whole round (K local SGDA steps AND the
  epilogue) on the quadratic workload (dx=384/dy=128/K=8), one row per
  lowering of ``make_round_step``.  ``dense_round`` is the per-leaf
  baseline (autodiff gradients, ~2× the flops of the affine form, one
  scan over K); ``pallas_packed_round`` swaps in the packed epilogue but
  keeps the scanned local steps; ``fused_round`` is the whole-round
  kernel of ``kernels/fused_round.py`` (K affine steps fused with the
  gossip matmuls — the lowering the ROADMAP's open item 2 asked for);
  ``fused_round_int8`` adds error-feedback int8-compressed gossip on top
  (what a real wire saves 4× on, ``core.compression``).
  ``fastest_timed`` is computed over these rows — the acceptance claim is
  that ``fused_round`` wins it, strictly under ``dense_round``.

* **Epilogue rows** (transformer-shaped state, many ragged leaves,
  ``epilogue_ms`` — deliberately NOT ``wall_ms``: an epilogue-only time
  on a different state is not comparable with a whole-round time): the
  gossip/correction/parameter-mixing epilogue of Algorithm 1 lines 7–11,
  lowered per-leaf (``dense``/``fused``), whole-state packed
  (``pallas_packed`` — the packed-xla oracle on this host), and sparse
  neighbor-gather (``sparse_packed``).  Each row also reports achieved
  HBM bandwidth (the epilogue moves 5·n·D·4 bytes: read Δ, θ, c; write
  θ', c') and, on a TPU, that as a fraction of the chip's HBM peak
  (``benchmarks.roofline.PEAKS``; a CPU run has no roofline share).
  ``pallas_packed_interpret`` — the actual Pallas kernel through the
  interpreter — is a *parity/smoke* row only: it validates the kernel
  against the oracle but its wall time measures the interpreter, so it
  stays out of both comparisons.

Also: a one-time ``block_d`` autotune for the epilogue kernel — sweeps
``kernels.ops.BLOCK_D_CANDIDATES`` for this (n, D), records the winner via
``ops.record_block_d`` (so ``fused_gossip_round(block_d=None)`` defaults to
it), and reports the sweep in the bench row.  On this CPU host the sweep
times the interpreter (relative block costs, not kernel truth); on a TPU the
same sweep times the compiled kernel.

Collective counts/bytes per lowering come from a 4-fake-CPU-device clients
mesh in a subprocess (the XLA host-device-count flag must precede jax's
first backend init).  ``--smoke`` skips the subprocess and the autotune.

CSV rows: ``gossip,impl=*_round,wall_ms=...``,
``gossip,impl=...,epilogue_ms=...,gbs=...,hbm_frac=...``,
``gossip,autotune,...``, ``gossip,impl=...,collectives=...``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import roofline
from repro.configs.base import AlgorithmConfig
from repro.core import mixing as mixing_lib
from repro.core import objectives, packing, topology
from repro.core import sparse_topology as sparse_lib
from repro.core.kgt_minimax import _tree_axpy, _tree_sub, init_state, \
    make_round_step
from repro.kernels import ops as kernel_ops

N_CLIENTS = 8
ETA_S, CORR = 0.5, 12.5  # η_s and 1/(K·η_c) stand-ins
# round-rows quadratic geometry: big enough that the K local steps dominate
ROUND_DX, ROUND_DY, ROUND_K = 384, 128, 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def synthetic_state(n: int = N_CLIENTS, d_model: int = 64, layers: int = 2,
                    seed: int = 0):
    """Client-stacked transformer-shaped pytree (many leaves, ragged sizes)."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i in range(layers):
        key, *ks = jax.random.split(key, 8)
        tree[f"layer{i}"] = {
            "q": jax.random.normal(ks[0], (n, d_model, d_model)),
            "k": jax.random.normal(ks[1], (n, d_model, d_model)),
            "v": jax.random.normal(ks[2], (n, d_model, d_model)),
            "o": jax.random.normal(ks[3], (n, d_model, d_model)),
            "up": jax.random.normal(ks[4], (n, d_model, 4 * d_model)),
            "down": jax.random.normal(ks[5], (n, 4 * d_model, d_model)),
            "norm": jax.random.normal(ks[6], (n, d_model)),
        }
    return tree


def epilogue_per_leaf(w, fused: bool):
    """The per-leaf lowering of kgt_minimax.round_step: one (dense) or half
    (fused: Δ and θ stacked into one collective) gossip launches per leaf,
    then the per-leaf correction/mixing axpy cascade."""

    def fn(dx, x, cx):
        if fused:
            pairs = jax.tree.map(lambda d, b: jnp.stack([d, b], axis=1), dx, x)
            mixed = mixing_lib.mix_dense(pairs, w)
            mdx = jax.tree.map(lambda p: p[:, 0], mixed)
            mx = jax.tree.map(lambda p: p[:, 1], mixed)
        else:
            mdx = mixing_lib.mix_dense(dx, w)
            mx = mixing_lib.mix_dense(x, w)
        cx_new = _tree_axpy(CORR, _tree_sub(dx, mdx), cx)
        x_new = _tree_axpy(ETA_S, mdx, mx)
        return x_new, cx_new

    return fn


def epilogue_packed(w, backend: str, block_d=None):
    """The fused-gossip round engine: ravel, one fused pass, unravel."""

    def fn(dx, x, cx):
        spec = packing.pack_spec(x)
        spec_c = packing.pack_spec(cx)
        xb, cb = kernel_ops.fused_gossip_round(
            w, packing.pack(dx, spec), packing.pack(x, spec),
            packing.pack(cx, spec_c), ETA_S, CORR, backend=backend,
            block_d=block_d)
        return packing.unpack(xb, spec), packing.unpack(cb, spec_c)

    return fn


def epilogue_sparse(w, backend: str):
    """Neighbor-gather lowering: same packed epilogue, W as padded-CSR."""
    sp = sparse_lib.from_dense(np.asarray(w))

    def fn(dx, x, cx):
        spec = packing.pack_spec(x)
        spec_c = packing.pack_spec(cx)
        xb, cb = kernel_ops.sparse_gossip_round(
            sp.neighbor_idx, sp.neighbor_w, sp.self_w,
            packing.pack(dx, spec), packing.pack(x, spec),
            packing.pack(cx, spec_c), ETA_S, CORR, backend=backend)
        return packing.unpack(xb, spec), packing.unpack(cb, spec_c)

    return fn


# Epilogue-only comparison (epilogue_ms); the interpret row is parity-only.
EPILOGUES = {
    "dense": lambda w: epilogue_per_leaf(w, fused=False),
    "fused": lambda w: epilogue_per_leaf(w, fused=True),
    "pallas_packed": lambda w: epilogue_packed(w, "xla"),
    "sparse_packed": lambda w: epilogue_sparse(w, "xla"),
}


def _time_ms(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _round_step_fn(impl: str, compress=None, seed: int = 0):
    """Whole-round program on the quadratic workload + its operands."""
    n, k = N_CLIENTS, ROUND_K
    key = jax.random.PRNGKey(seed)
    data = objectives.make_quadratic_data(key, n, dx=ROUND_DX, dy=ROUND_DY,
                                          heterogeneity=1.0)
    problem = objectives.quadratic_problem(data, sigma=0.0)
    cfg = AlgorithmConfig(
        algorithm="kgt_minimax", num_clients=n, local_steps=k,
        eta_cx=0.01, eta_cy=0.05, topology="exp", mixing_impl=impl,
        gossip_backend="xla", gossip_compress=compress)
    batch = {key_: data[key_] for key_ in ("A", "B", "b", "q")}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (k, *v.shape)),
                      batch)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), k * n).reshape(
        k, n, 2).astype(jnp.uint32)
    st = init_state(problem, cfg, key, init_batch=batch, init_keys=keys[0])
    step = jax.jit(make_round_step(problem, cfg))
    return step, (st, kb, keys)


def _autotune_block_d(w, dx, x, cx, csv, results: dict) -> None:
    """One-time block_d sweep for the epilogue kernel at this (n, D):
    record the winner so ``fused_gossip_round(block_d=None)`` defaults to
    the measured best instead of the hardcoded 512."""
    spec = packing.pack_spec(x)
    n, d = spec.n, spec.dim
    sweep = {}
    for blk in kernel_ops.BLOCK_D_CANDIDATES:
        fn = jax.jit(epilogue_packed(w, "interpret", block_d=blk))
        sweep[blk] = _time_ms(fn, (dx, x, cx), reps=1)
    best = min(sweep, key=sweep.get)
    kernel_ops.record_block_d(n, d, best)
    csv("gossip,autotune,block_d=" + str(best) + ","
        + ",".join(f"ms_{b}={m:.1f}" for b, m in sorted(sweep.items())))
    results["autotune"] = {"n": n, "packed_D": d, "best_block_d": best,
                           "sweep_ms": {str(b): round(m, 2)
                                        for b, m in sweep.items()}}


def collective_counts_child() -> None:
    """Child mode (fake 4-device mesh already forced via XLA_FLAGS): compile
    each epilogue with the clients dim mesh-sharded and count collectives."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.analysis import hlo_cost

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("clients",))
    w = jnp.asarray(topology.mixing_matrix("exp", n), jnp.float32)
    x = synthetic_state(n=n, d_model=16, layers=2)
    dx = jax.tree.map(lambda v: v * 0.01, x)
    cx = jax.tree.map(jnp.zeros_like, x)
    shard = jax.tree.map(lambda v: NamedSharding(mesh, P("clients")), x)

    out = {}
    for name in ("dense", "fused", "pallas_packed"):
        fn = jax.jit(EPILOGUES[name](w), in_shardings=(shard, shard, shard))
        txt = fn.lower(dx, x, cx).compile().as_text()
        cost = hlo_cost.analyze(txt)
        out[name] = {
            "collectives": int(sum(cost.collective_counts.values())),
            "by_kind": {k: int(v) for k, v in cost.collective_counts.items()
                        if v},
            "collective_mb": round(cost.total_collective_bytes() / 1e6, 3),
        }
    print("JSON:" + json.dumps(out), flush=True)


def _collectives_via_subprocess() -> dict:
    env = dict(os.environ)
    # CPU-only: the count needs 4 fake devices, and a child that reached for
    # the accelerator would contend with this process for it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_gossip", "--collectives-child"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"collectives child failed:\n{proc.stdout[-2000:]}"
                           f"\n{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("JSON:"):
            return json.loads(line[len("JSON:"):])
    raise RuntimeError(f"no JSON line in child output: {proc.stdout[-500:]}")


def run(csv=print, smoke: bool = False) -> dict:
    w = jnp.asarray(topology.mixing_matrix("exp", N_CLIENTS), jnp.float32)
    x = synthetic_state()
    dx = jax.tree.map(lambda v: v * 0.01, x)
    cx = jax.tree.map(jnp.zeros_like, x)
    spec = packing.pack_spec(x)
    results: dict = {"n": N_CLIENTS, "leaves": len(jax.tree.leaves(x)),
                     "packed_D": spec.dim}
    # what the epilogue moves through memory: read Δ, θ, c; write θ', c'
    epilogue_bytes = 5 * spec.n * spec.dim * 4

    if not smoke:
        _autotune_block_d(w, dx, x, cx, csv, results)

    # a roofline share exists only on a chip with published peaks
    dev = jax.devices()[0]
    hbm = (roofline.peaks(dev.device_kind)["hbm_bytes_per_s"]
           if dev.platform == "tpu" else None)
    for name, builder in EPILOGUES.items():
        reps = 2 if smoke else 20
        ms = _time_ms(jax.jit(builder(w)), (dx, x, cx), reps)
        gbs = epilogue_bytes / (ms / 1e3) / 1e9
        row = {"epilogue_ms": round(ms, 3), "achieved_gbs": round(gbs, 2)}
        if hbm is not None:
            row["hbm_frac"] = round(gbs / (hbm / 1e9), 4)
        share = (f"hbm_frac={row['hbm_frac']:.3f},"
                 if "hbm_frac" in row else "")
        csv(f"gossip,impl={name},epilogue_ms={ms:.2f},gbs={gbs:.1f},{share}"
            f"n={N_CLIENTS},leaves={results['leaves']},packed_D={spec.dim}")
        results[name] = row

    # Pallas-kernel parity (interpret mode): validation, never a speed row —
    # the interpreter's wall time says nothing about the compiled kernel.
    ref = jax.jit(EPILOGUES["pallas_packed"](w))(dx, x, cx)
    got = jax.jit(epilogue_packed(w, "interpret"))(dx, x, cx)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)))
    csv(f"gossip,impl=pallas_packed_interpret,parity_max_err={err:.2e},"
        f"parity_ok={int(err <= 1e-6)}")
    results["pallas_packed_interpret"] = {
        "parity_max_err": err, "parity_ok": bool(err <= 1e-6)}
    if err > 1e-6:
        raise AssertionError(
            f"pallas_packed interpret/xla parity broke: max err {err:.3e}")

    # Whole-round rows: K local steps + epilogue, quadratic workload.
    round_rows = [("dense_round", "dense", None),
                  ("pallas_packed_round", "pallas_packed", None),
                  ("fused_round", "fused_round", None),
                  ("fused_round_int8", "fused_round", "int8")]
    for row, impl, compress in round_rows:
        step, (st, kb, keys) = _round_step_fn(impl, compress)
        ms = _time_ms(step, (st, kb, keys), 2 if smoke else 20)
        csv(f"gossip,impl={row},wall_ms={ms:.2f},workload=quadratic,"
            f"dz={ROUND_DX + ROUND_DY},K={ROUND_K},n={N_CLIENTS}")
        results[row] = {"wall_ms": round(ms, 3), "workload": "quadratic",
                        "dz": ROUND_DX + ROUND_DY, "K": ROUND_K}
        if compress:
            from repro.kernels.quantize import wire_bits
            results[row]["wire_bits"] = wire_bits(compress)

    timed = [k for k in results
             if isinstance(results[k], dict) and "wall_ms" in results[k]]
    fastest = min(timed, key=lambda k: results[k]["wall_ms"])
    results["fastest_timed"] = fastest
    csv(f"gossip,fastest_timed={fastest},"
        f"wall_ms={results[fastest]['wall_ms']}")

    if not smoke:
        for name, c in _collectives_via_subprocess().items():
            kinds = ";".join(f"{k}:{v}" for k, v in sorted(c["by_kind"].items()))
            csv(f"gossip,impl={name},collectives={c['collectives']},"
                f"collective_mb={c['collective_mb']},kinds={kinds}")
            results.setdefault(name, {}).update(c)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--collectives-child", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: fewer reps, skip the collectives "
                         "subprocess and the block_d autotune")
    args = ap.parse_args()
    if args.collectives_child:
        collective_counts_child()
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
