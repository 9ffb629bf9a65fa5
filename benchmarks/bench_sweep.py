"""Sweep throughput: sequential per-point loop vs one vmapped batched cell.

The workload is a 16-seed replicate cell of the paper's toy quadratic
(n=8, K=4, σ=1) run for a fixed 256 rounds (eps=0 so neither path
early-stops).  The sequential path is what the benchmarks did before the
sweep subsystem: drive ``run_to_epsilon`` once per point — one fresh
compile *and* one chunk dispatch per ``eval_every`` interval per point.
The batched path runs the identical 16 trajectories as one
``repro.sweep.batched`` cell: one compile, one chunk dispatch per interval
for the whole batch (the trajectories are bit-identical — that is a test,
not a benchmark claim; see tests/test_sweep.py).

Headline metric: end-to-end trajectories/s — the throughput a sweep user
experiences, where the sequential loop pays one XLA compilation *per point*
(the exact cost ISSUE-4 calls out) and the batched cell compiles once.
Steady-state ``run_s`` throughput (compile and setup split out on both
sides, per the timing satellite) is reported alongside: on this CPU the
vmapped scan's run-only win is bounded by how sublinearly XLA scales the
tiny quadratic ops with batch width, so most of the batched win at this
problem size is amortized compilation; on accelerators the width is free.

The shared per-point *setup* program (``prepare_trajectory``) is warmed
before either path is timed — it is cached process-wide and would otherwise
bill its one-time compile to whichever path ran first.

A third comparison proves the persistent compile cache
(``repro.sweep.cache``): the same batched cell runs in two fresh
subprocesses sharing one cache directory (``results/.bench_sweep_cache``,
emptied first; jax's layer stays at ``$JAX_COMPILATION_CACHE_DIR`` where
that is set) — ``cold_cache`` pays the real
compiles and populates the cache, ``warm_cache`` deserializes executables
from disk.  The rows record the warm run's compile fraction (the ISSUE-10
acceptance bar: < 10% of wall) and that its per-point results are
bit-identical to the cold run's.

CSV rows: ``sweep,mode=...,traj_per_s=...,traj_rounds_per_s=...``.
"""
from __future__ import annotations

import json
import os
import subprocess
import shutil
import sys
import time

from repro.sweep import grid, run as sweep_run

B = 16
ROUNDS = 256
EVAL_EVERY = 16

SPEC = grid.GridSpec(
    name="bench_sweep",
    base=dict(n=8, K=4, sigma=1.0, heterogeneity=0.5, topology="ring",
              eta_cx=0.01, eta_cy=0.1, eta_s=0.5, eps=0.0,
              max_rounds=ROUNDS, eval_every=EVAL_EVERY),
    axes=(grid.batch_axis("seed", *range(B)),),
)


def _cache_child(cache_dir: str) -> dict:
    """One fresh-process run of the batched cell against ``cache_dir`` —
    the cold/warm halves of the cache benchmark (invoked via
    ``python -m benchmarks.bench_sweep --cache-child DIR``)."""
    from repro.sweep import cache as cache_lib

    cache_lib.enable_xla_cache(os.path.join(cache_dir, "xla"))
    cache = cache_lib.CompileCache(os.path.join(cache_dir, "aot"))
    [cell] = SPEC.cells()
    results, timing = sweep_run.run_cell(cell, cache=cache)
    return {
        "timing": timing,
        "stats": dict(cache.stats),
        # full float precision round-trips through JSON repr — the parent
        # compares these for bit-identity
        "results": [{"final_grad": r["final_grad"], "history": r["history"]}
                    for r in results],
    }


#: The cold/warm pair's cache root: fixed, so the warm half finds what the
#: cold half wrote, and cleared before the cold half.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", ".bench_sweep_cache")


def _cache_pair(csv) -> dict:
    """Run the cell in two fresh subprocesses sharing one cache directory
    and report cold-vs-warm timing + bit-identity.  Called before this
    process touches a JAX backend, so on an accelerator the children are
    the only holders of the chip."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    # the children get an explicit dir, and both layers start empty there
    env.pop("REPRO_COMPILE_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    shutil.rmtree(CACHE_DIR, ignore_errors=True)
    out: dict = {}
    for mode in ("cold_cache", "warm_cache"):
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_sweep",
             "--cache-child", CACHE_DIR],
            capture_output=True, text=True, cwd=root, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"cache child ({mode}) failed:\n{proc.stderr}")
        rec = json.loads(proc.stdout)
        t = rec["timing"]
        frac = (t["compile_s"] / t["wall_s"]) if t["wall_s"] else 0.0
        rec["compile_frac"] = round(frac, 3)
        csv(f"sweep,mode={mode},B={B},rounds={ROUNDS},"
            f"wall_s={t['wall_s']},compile_s={t['compile_s']},"
            f"run_s={t['run_s']},compile_frac={rec['compile_frac']},"
            f"cache_hits={int(rec['stats']['hits'])},"
            f"cache_misses={int(rec['stats']['misses'])}")
        out[mode] = rec
    identical = out["cold_cache"]["results"] == out["warm_cache"]["results"]
    out["bit_identical"] = identical
    out["warm_compile_frac"] = out["warm_cache"]["compile_frac"]
    csv(f"sweep,summary_cache,warm_compile_frac={out['warm_compile_frac']},"
        f"bit_identical={identical}")
    for mode in ("cold_cache", "warm_cache"):
        del out[mode]["results"]  # bulky; identity is already asserted
    return out


def run(csv=print) -> dict:
    # the cold/warm children first: after this process has run a cell it
    # holds the backend (on an accelerator, the chip)
    cache_pair = _cache_pair(csv)
    [cell] = SPEC.cells()
    sweep_run.prepare_trajectory(cell.points[0])  # warm the shared preparer

    # batched: the whole cell as one vmapped program (cache off: these two
    # rows isolate batching, not persistence — the cache rows follow)
    t0 = time.perf_counter()
    results, bt = sweep_run.run_cell(cell, cache=None)
    batched_wall = time.perf_counter() - t0
    assert all(r["history"][-1][0] == ROUNDS for r in results)
    batched_tps = B / batched_wall
    batched_rps = B * ROUNDS / bt["run_s"]
    csv(f"sweep,mode=batched,B={B},rounds={ROUNDS},"
        f"traj_per_s={batched_tps:.2f},traj_rounds_per_s={batched_rps:.0f},"
        f"compile_s={bt['compile_s']},run_s={bt['run_s']}")

    # sequential: one run_point per trajectory — the pre-sweep benchmark
    # execution model, which recompiles its programs for every point
    # (run_point builds fresh jit closures each call, exactly as the
    # historical run_to_epsilon did)
    t0 = time.perf_counter()
    seq_run_s = seq_compile_s = seq_setup_s = 0.0
    for p in cell.points:
        hit, final, timing, hist = sweep_run.run_point(p, cache=None)
        seq_run_s += timing["run_s"]
        seq_compile_s += timing["compile_s"]
        seq_setup_s += timing["setup_s"]
    seq_wall = time.perf_counter() - t0
    seq_tps = B / seq_wall
    seq_rps = B * ROUNDS / seq_run_s
    csv(f"sweep,mode=sequential,B={B},rounds={ROUNDS},"
        f"traj_per_s={seq_tps:.2f},traj_rounds_per_s={seq_rps:.0f},"
        f"compile_s={seq_compile_s:.2f},run_s={seq_run_s:.2f}")

    speedup = seq_wall / batched_wall
    speedup_run = batched_rps / seq_rps
    csv(f"sweep,summary,speedup_traj_per_s={speedup:.2f}x,"
        f"speedup_run_only={speedup_run:.2f}x")
    return {
        "B": B, "rounds": ROUNDS, "eval_every": EVAL_EVERY,
        "cache": cache_pair,
        "batched": {"traj_per_s": round(batched_tps, 2),
                    "traj_rounds_per_s": round(batched_rps, 1),
                    "wall_s": round(batched_wall, 3), **bt},
        "sequential": {
            "traj_per_s": round(seq_tps, 2),
            "traj_rounds_per_s": round(seq_rps, 1),
            "wall_s": round(seq_wall, 3),
            "compile_s": round(seq_compile_s, 3),
            "setup_s": round(seq_setup_s, 3),
            "run_s": round(seq_run_s, 3),
        },
        "speedup_traj_per_s": round(speedup, 2),
        "speedup_run_only": round(speedup_run, 2),
    }


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--cache-child":
        print(json.dumps(_cache_child(sys.argv[2])))
    else:
        run()
