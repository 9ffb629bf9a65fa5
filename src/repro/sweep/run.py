"""Sweep runner + CLI: drive whole hyperparameter grids as compiled cells.

A *point* is one experiment configuration — the kwargs of the historical
``benchmarks.common.run_to_epsilon`` (synthetic NC-SC quadratic, exact ∇Φ
oracle, rounds-to-ε on an ``eval_every`` grid).  :func:`run_point` executes
one point sequentially; :func:`run_cell` executes a whole static cell as a
single vmapped scan program (`repro.sweep.batched`), with one dispatch per
``eval_every`` chunk for the entire batch and the per-trajectory early-stop
mask freezing converged trajectories at exactly the boundary the sequential
``stop_fn`` would have stopped.  Both paths jit the *same* unbatched
trajectory program, so their trajectories are bit-identical
(tests/test_sweep.py holds every cell of small grids to that).

  PYTHONPATH=src python -m repro.sweep.run smoke           # tiny end-to-end
  PYTHONPATH=src python -m repro.sweep.run local_steps topology
  PYTHONPATH=src python -m repro.sweep.run --list
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as engine_lib
from repro.configs.base import AlgorithmConfig
from repro.core import (
    init_state,
    make_quadratic_data,
    make_round_step,
    mixing_matrix,
    point_etas,
    quadratic_cell_problem,
    sparse_mixing_matrix,
)
from repro.sweep import batched as batched_lib
from repro.sweep import cache as cache_lib
from repro.sweep import grid as grid_lib
from repro.sweep import store as store_lib

DX, DY = 10, 5  # the benchmarks' quadratic geometry (benchmarks.common)

# One-configuration defaults == run_to_epsilon's signature defaults.
# topology_family/edge_prob/client_drop_prob/participation are the churn
# axes (repro.core.stochastic_topology): family "static" + participation 1.0
# is the historical fixed-W full-participation point.
DEFAULT_POINT: Dict[str, Any] = dict(
    n=8, K=4, sigma=0.1, heterogeneity=1.0, topology="ring",
    algorithm="kgt_minimax", eta_cx=0.01, eta_cy=0.1, eta_s=0.5,
    eps=0.3, max_rounds=2000, seed=0, mixing_impl="dense", eval_every=10,
    topology_family="static", edge_prob=0.5, client_drop_prob=0.3,
    participation=1.0,
    num_byzantine=0, attack="honest", attack_scale=1.0, robust_trim=1,
    gossip_compress=None, gossip_backend="auto",
)

# Point parameters that change the traced program: same-valued across every
# point of a cell, enforced at cell build time.  (sigma is special-cased:
# its *value* is a leaf but sigma>0 toggles the noise ops — grid axes over
# sigma must declare ``cell_key=lambda s: s > 0``.  participation is the
# same shape: the rate is a leaf, but participation<1 toggles the mask ops —
# axes spanning 1.0 declare ``cell_key=lambda r: r < 1``.  num_byzantine is
# too: the count/attack id/scale are traced bundle leaves, but f>0 toggles
# the adversary extras slot — axes spanning 0 declare
# ``cell_key=lambda f: f > 0``.)
STATIC_KEYS = ("algorithm", "n", "K", "topology", "mixing_impl",
               "eps", "max_rounds", "eval_every", "topology_family",
               "robust_trim", "gossip_compress", "gossip_backend")


def _churn(p: Dict[str, Any]):
    """(samples W per round, applies a participation mask) — both static
    program properties of a cell."""
    return p["topology_family"] != "static", p["participation"] < 1.0


def _byz(p: Dict[str, Any]) -> bool:
    """Whether the cell carries the Byzantine adversary extras slot —
    a static program property (extras arity)."""
    return p["num_byzantine"] > 0


def _program_statics(p: Dict[str, Any], *, batched: bool) -> tuple:
    """The persistent-cache statics signature of a point's traced program —
    exactly the parameters baked into the jaxpr as constants or structure.
    Deliberately narrower than :data:`STATIC_KEYS`: ``eps`` is host-side
    and ``max_rounds``/``eval_every`` only choose operand values and chunk
    lengths (keyed separately), so cells differing only in those share
    executables."""
    return (
        ("algorithm", p["algorithm"]), ("n", p["n"]), ("K", p["K"]),
        ("topology", p["topology"]), ("mixing_impl", p["mixing_impl"]),
        ("topology_family", p["topology_family"]),
        ("robust_trim", p["robust_trim"]),
        ("gossip_compress", p["gossip_compress"]),
        ("gossip_backend", p["gossip_backend"]),
        ("noise", p["sigma"] > 0.0), ("churn", _churn(p)),
        ("byzantine", _byz(p)), ("batched", batched),
        ("geometry", (DX, DY)),
    )


def _full_point(p: Dict[str, Any]) -> Dict[str, Any]:
    full = dict(DEFAULT_POINT)
    unknown = set(p) - set(full)
    if unknown:
        raise ValueError(f"unknown point parameters {sorted(unknown)}")
    full.update(p)
    return full


def _cfg(p: Dict[str, Any]) -> AlgorithmConfig:
    return AlgorithmConfig(
        algorithm=p["algorithm"], num_clients=p["n"], local_steps=p["K"],
        eta_cx=p["eta_cx"], eta_cy=p["eta_cy"], eta_sx=p["eta_s"],
        eta_sy=p["eta_s"], topology=p["topology"],
        mixing_impl=p["mixing_impl"], robust_trim=p["robust_trim"],
        gossip_compress=p["gossip_compress"],
        gossip_backend=p["gossip_backend"])


# Jitted per-point setup, cached on the static parameters it bakes in.
# Seed / heterogeneity / sigma are traced operands, so one compile serves
# every point of a cell (and any cell sharing the statics) — eager setup
# was ~2s/point of small-op dispatch, the dominant cost of small sweeps.
_PREPARERS: Dict[tuple, Any] = {}


def _preparer(p: Dict[str, Any]):
    noise = p["sigma"] > 0.0
    # gossip_compress changes the state *structure* (EF leaves), so it must
    # key the cached init program alongside the other structural statics
    cache_key = (p["n"], p["algorithm"], noise, p["gossip_compress"])
    if cache_key in _PREPARERS:
        return _PREPARERS[cache_key]
    problem = quadratic_cell_problem(DX, DY, mu=1.0, noise=noise)
    cfg = _cfg(p)  # init_state only reads algorithm/num_clients/dtype

    def prep(seed, het, sigma):
        key = jax.random.PRNGKey(seed)
        data = make_quadratic_data(key, p["n"], dx=DX, dy=DY,
                                   heterogeneity=het)
        cb = {k: v for k, v in data.items() if k != "mu"}
        if noise:
            cb = dict(cb, sigma=jnp.full((p["n"],), sigma, jnp.float32))
        st = init_state(problem, cfg, key, init_batch=cb,
                        init_keys=jax.random.split(key, p["n"]))
        consts = {
            "a_bar": data["A"].mean(0), "b_bar": data["B"].mean(0),
            "bv_bar": data["b"].mean(0), "q_bar": data["q"].mean(0),
        }
        return st, cb, consts

    _PREPARERS[cache_key] = jax.jit(prep)
    return _PREPARERS[cache_key]


def prepare_trajectory(p: Dict[str, Any], *, cache=None):
    """One point -> (Trajectories, phi-oracle constants).

    The historical ``run_to_epsilon`` recipe — data and problem from
    ``PRNGKey(seed)``, shared x0/y0, tracking corrections from the init
    batch — as one jitted program shared by the sequential and batched
    paths, so trajectory starts are bit-identical by construction.  The phi
    constants are the client-mean coefficients the exact ∇Φ oracle needs
    (the cell problem reads per-client slices from the batch and has no
    global view).  ``cache`` (a ``repro.sweep.cache.CompileCache``) serves
    the jitted setup program from the persistent executable cache — its
    statics are ``_PREPARERS``' key (seed/het/sigma are traced operands).
    """
    p = _full_point(p)
    prep = _preparer(p)
    args = (jnp.int32(p["seed"]), jnp.float32(p["heterogeneity"]),
            jnp.float32(p["sigma"]))
    if cache is not None:
        prep, _ = cache.get_or_compile(
            "preparer",
            (("n", p["n"]), ("algorithm", p["algorithm"]),
             ("noise", p["sigma"] > 0.0),
             ("gossip_compress", p["gossip_compress"]),
             ("geometry", (DX, DY))),
            prep, args)
    st, cb, consts = prep(*args)
    kb = jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (p["K"], *v.shape)), cb)
    random_w, part = _churn(p)
    topo = None
    if random_w or part or _byz(p):
        from repro.core import adversary as adversary_lib

        topo = {"seed": jnp.int32(p["seed"]),
                "edge_prob": jnp.float32(p["edge_prob"]),
                "drop_prob": jnp.float32(p["client_drop_prob"]),
                "rate": jnp.float32(p["participation"]),
                "num_byzantine": jnp.int32(p["num_byzantine"]),
                "attack_id": jnp.int32(
                    adversary_lib.ATTACK_IDS[p["attack"]]),
                "attack_scale": jnp.float32(p["attack_scale"])}
    traj = batched_lib.Trajectories(
        state=st, batches=kb, etas=point_etas(_cfg(p)),
        seed=jnp.int32(p["seed"]), active=jnp.asarray(True), topo=topo)
    return traj, consts


def _phi_grad_norm(consts, x_clients, mu: float):
    """Exact ‖∇Φ(x̄)‖ from the client-mean constants — the expression of
    ``quadratic_problem.phi_grad`` + ``phi_grad_norm``, term for term."""
    x = x_clients.mean(0)
    ystar = (consts["b_bar"] @ x + consts["bv_bar"]) / mu
    g = consts["a_bar"] @ x + consts["q_bar"] + consts["b_bar"].T @ ystar
    return jnp.sqrt(jnp.sum(jnp.square(g)))


def _cell_programs(p: Dict[str, Any], *, batched: bool, mesh=None,
                   mesh_axis: str = batched_lib.CLIENTS):
    """(chunk builder, eval fn) for a cell whose static parameters are
    ``p``'s.  ``batched`` selects vmap-of-the-trajectory-program vs the
    unbatched sequential reference — the *only* difference between the two
    execution paths.

    The ∇Φ convergence oracle is deliberately a single-trajectory program
    on both paths: XLA's fusion of this small matvec chain is not
    vmap-rounding-stable (an ulp here flips a ``g < eps`` stop decision
    near the threshold), so the batched driver dispatches the same cached
    executable per active trajectory at chunk boundaries instead of
    vmapping it.  The scan chunk — where the round compute lives — stays
    one dispatch for the whole batch, and *is* bit-stable under vmap
    (held to that by tests/test_sweep.py).
    """
    noise = p["sigma"] > 0.0
    problem = quadratic_cell_problem(DX, DY, mu=1.0, noise=noise)
    random_w, part = _churn(p)
    byz = _byz(p)
    round_step = make_round_step(problem, _cfg(p), traced_etas=True,
                                 traced_w=random_w, participation=part,
                                 byzantine=byz)
    if random_w or part or byz:
        if p["mixing_impl"].startswith("sparse_"):
            # the W extras slot carries a SparseTopology pytree — the draw
            # happens on the neighbor lists of the configured support graph,
            # never through an (n, n) array
            support = sparse_mixing_matrix(p["topology"], p["n"])
            sampler = batched_lib.make_churn_traj_sampler(
                local_steps=p["K"], num_clients=p["n"],
                family=p["topology_family"], participation=part,
                sparse_support=support, byzantine=byz)
        else:
            base_w = (mixing_matrix(p["topology"], p["n"])
                      if p["topology_family"] in ("static", "dropout")
                      else None)
            sampler = batched_lib.make_churn_traj_sampler(
                local_steps=p["K"], num_clients=p["n"],
                family=p["topology_family"], base_w=base_w,
                participation=part, byzantine=byz)
    else:
        sampler = batched_lib.make_quadratic_traj_sampler(
            local_steps=p["K"], num_clients=p["n"])
    if batched:
        build = batched_lib.make_batched_chunk_builder(
            round_step, sampler, mesh=mesh, mesh_axis=mesh_axis)
    else:
        build = batched_lib.make_trajectory_chunk_builder(round_step, sampler)
    eval_fn = jax.jit(lambda c, x: _phi_grad_norm(c, x, 1.0))
    return build, eval_fn


def _timed_eval(eval_fn, *, cache=None, statics=None, telemetry=None):
    """AOT-compile ``eval_fn`` on first use, reporting the compile seconds
    (same split discipline as ``engine.timed_chunk_builder``).  With a
    ``cache`` the executable is served from/stored to the persistent
    compile cache under kind ``"phi_eval"``.

    A failed AOT compile falls back to the on-demand jit — loudly (stderr +
    an ``eval_aot_fallback`` telemetry counter), and *without* charging the
    failed attempt to ``compile_s``: the on-demand path re-traces inside the
    first real call, so attributing the aborted lower() time would
    double-count against ``run_s``.
    """
    holder: dict = {}

    def call(*args):
        if "fn" not in holder:
            if cache is not None:
                fn, info = cache.get_or_compile("phi_eval", statics,
                                                eval_fn, args)
                holder["fn"] = fn
                holder["compile_s"] = (info["compile_s"]
                                       + info["deserialize_s"])
            else:
                t0 = time.perf_counter()
                try:
                    holder["fn"] = eval_fn.lower(*args).compile()
                    holder["compile_s"] = time.perf_counter() - t0
                except Exception as e:
                    holder["fn"] = eval_fn
                    holder["compile_s"] = 0.0
                    print(f"[sweep] eval AOT compile failed "
                          f"({type(e).__name__}: {e}); falling back to "
                          "on-demand jit", file=sys.stderr, flush=True)
                    if telemetry is not None:
                        telemetry.counter("eval_aot_fallback", 1,
                                          error=type(e).__name__)
        return holder["fn"](*args)

    call.stats = holder
    return call


def _timing_split(wall: float, compile_s: float, setup_s: float) -> dict:
    """The ``{wall_s, compile_s, setup_s, run_s}`` record with the engine's
    rounding discipline: ms-grained, and ``run_s`` clamped at zero — the
    subtraction runs over three separately-measured intervals, so rounding
    jitter (or a cache making compile_s ≈ wall) must not surface as a
    negative runtime."""
    return {"wall_s": round(wall, 3), "compile_s": round(compile_s, 3),
            "setup_s": round(setup_s, 3),
            "run_s": max(0.0, round(wall - compile_s - setup_s, 3))}


def _chunk_lengths(length: int, cache) -> tuple:
    """The sub-chunk schedule for one ``eval_every`` interval: the
    power-of-two bucket decomposition when a cache wants length sharing
    (bit-exact — scan chunks compose through the carried state), the plain
    length otherwise."""
    if cache is not None and cache.bucket_lengths:
        return cache_lib.length_schedule(length)
    return (length,)


def point_program_text(p: Dict[str, Any], length: int) -> str:
    """The optimized HLO of the ``length``-round chunk program that
    :func:`run_point` compiles for ``p`` — where a caller checks which
    lowering the backend really got (e.g. a ``tpu_custom_call`` for a
    Pallas kernel)."""
    p = _full_point(p)
    traj, _ = prepare_trajectory(p)
    build, _ = _cell_programs(p, batched=False)
    final_round = jnp.int32(p["max_rounds"] - 1)
    return build(length).lower(traj, final_round).compile().as_text()


def run_point(p: Dict[str, Any], *, cache=cache_lib.UNSET, telemetry=None):
    """Sequential reference: one point, engine-chunked scan per
    ``eval_every`` interval, ∇Φ checked at chunk boundaries with immediate
    stop — the execution `benchmarks.common.run_to_epsilon` delegates to.

    Returns ``(rounds_to_eps or None, final ‖∇Φ‖, timing, history)`` where
    ``timing = {"wall_s", "compile_s", "setup_s", "run_s"}`` splits XLA
    compilation from steady-state execution and ``history`` is
    ``[(round, grad), …]`` on the evaluation grid.

    ``cache`` is a ``repro.sweep.cache.CompileCache`` (default: resolved
    from ``$REPRO_COMPILE_CACHE``; ``None`` disables): the setup, chunk,
    and eval executables are served from disk when warm, and chunk lengths
    are served from the shared power-of-two pool.
    """
    p = _full_point(p)
    cache = cache_lib.resolve(cache, telemetry)
    t0 = time.perf_counter()
    traj, consts = prepare_trajectory(p, cache=cache)
    jax.block_until_ready(traj.state.x)
    setup_s = time.perf_counter() - t0
    statics = _program_statics(p, batched=False)
    build_raw, eval_raw = _cell_programs(p, batched=False)
    build = engine_lib.timed_chunk_builder(build_raw, cache=cache,
                                           statics=statics)
    eval_fn = _timed_eval(eval_raw, cache=cache,
                          statics=(("kind", "phi"), ("geometry", (DX, DY)),
                                   ("n", p["n"])),
                          telemetry=telemetry)
    hist: List[tuple] = []
    hit = None
    final_round = jnp.int32(p["max_rounds"] - 1)
    r = 0
    while r < p["max_rounds"]:
        length = min(p["eval_every"], p["max_rounds"] - r)
        for sub in _chunk_lengths(length, cache):
            traj, _ = build(sub)(traj, final_round)
        r += length
        g = float(eval_fn(consts, traj.state.x))
        hist.append((r, g))
        if g < p["eps"]:
            hit = r
            break
    final = hist[-1][1] if hist else float("nan")
    wall = time.perf_counter() - t0
    compile_s = build.stats["compile_s"] + eval_fn.stats.get("compile_s", 0.0)
    timing = _timing_split(wall, compile_s, setup_s)
    return hit, final, timing, hist


def run_cell(cell: grid_lib.Cell, *, mesh=None,
             mesh_axis: str = batched_lib.CLIENTS,
             return_trajs: bool = False, cache=cache_lib.UNSET,
             telemetry=None):
    """One static cell as a batched program: returns
    ``(per-point result dicts, timing)`` — with ``return_trajs``,
    ``((results, timing), trajectories)`` including the final stacked
    (frozen-where-converged) state.

    Drives the same evaluation grid as :func:`run_point`: after each
    ``eval_every`` chunk the batched ∇Φ oracle runs once for all B
    trajectories, newly-converged ones record their hit round and drop out
    of the ``active`` mask (their state freezes at this exact boundary),
    and the loop exits early once every trajectory has converged.

    With a compile ``cache`` (default: ``$REPRO_COMPILE_CACHE``) the cell's
    executables persist across processes, and the trajectory batch is
    padded up to its :func:`repro.sweep.cache.bucket_batch` bucket with
    ``active=False`` clones of trajectory 0, so cells differing only in
    point count share one vmapped program — real rows are bit-identical
    (vmap slice stability, pinned by tests) and results are sliced back to
    the real batch.  Under a ``mesh`` the AOT/bucket layers are skipped
    (padding would change the sharding divisibility and serialized
    executables embed their device assignment); jax's own persistent cache
    (layer 1) still applies.
    """
    points = [_full_point(p) for p in cell.points]
    p0 = points[0]
    for p in points[1:]:
        bad = [k for k in STATIC_KEYS if p[k] != p0[k]]
        if (p["sigma"] > 0.0) != (p0["sigma"] > 0.0):
            bad.append("sigma>0")
        if _churn(p) != _churn(p0):
            bad.append("participation<1")
        if _byz(p) != _byz(p0):
            bad.append("num_byzantine>0")
        if bad:
            raise ValueError(
                f"cell {cell.key!r} mixes static program parameters {bad}; "
                "declare them as static axes (or give the sigma axis "
                "cell_key=lambda s: s > 0, a participation axis spanning "
                "1.0 cell_key=lambda r: r < 1)")

    cache = cache_lib.resolve(cache, telemetry)
    if mesh is not None:
        cache = None  # layer 1 (jax's own cache) still applies
    t0 = time.perf_counter()
    prepared = [prepare_trajectory(p, cache=cache) for p in points]
    trajs = batched_lib.tree_stack([tr for tr, _ in prepared])
    consts = [c for _, c in prepared]  # per-trajectory, never stacked
    B = len(points)
    pad = 0
    if cache is not None and cache.bucket_batch:
        pad = cache_lib.bucket_batch(B) - B
        trajs = cache_lib.pad_trajectories(trajs, pad)
    jax.block_until_ready(trajs.state.x)
    setup_s = time.perf_counter() - t0
    if mesh is not None:
        trajs = jax.device_put(trajs, batched_lib.batch_sharding(mesh, mesh_axis))
    build_raw, eval_raw = _cell_programs(p0, batched=True, mesh=mesh,
                                         mesh_axis=mesh_axis)
    build = engine_lib.timed_chunk_builder(
        build_raw, cache=cache, statics=_program_statics(p0, batched=True))
    eval_fn = _timed_eval(eval_raw, cache=cache,
                          statics=(("kind", "phi"), ("geometry", (DX, DY)),
                                   ("n", p0["n"])),
                          telemetry=telemetry)

    active = np.ones(B, bool)
    hit: List[Optional[int]] = [None] * B
    hist: List[List[tuple]] = [[] for _ in range(B)]
    final_round = jnp.int32(p0["max_rounds"] - 1)

    def full_mask(live):
        # padding rows stay frozen (False) for the whole run
        return jnp.asarray(np.concatenate([live, np.zeros(pad, bool)])
                           if pad else live)

    r = 0
    while r < p0["max_rounds"]:
        length = min(p0["eval_every"], p0["max_rounds"] - r)
        for sub in _chunk_lengths(length, cache):
            trajs, _ = build(sub)(trajs, final_round)
        r += length
        # dispatch the oracle for every live trajectory, then sync once
        g = {i: eval_fn(consts[i], trajs.state.x[i])
             for i in range(B) if active[i]}
        for i, gi in g.items():
            gi = float(gi)
            hist[i].append((r, gi))
            if gi < points[i]["eps"]:
                hit[i] = r
                active[i] = False
        if not active.any():
            break
        trajs = dataclasses.replace(trajs, active=full_mask(active))

    wall = time.perf_counter() - t0
    compile_s = build.stats["compile_s"] + eval_fn.stats.get("compile_s", 0.0)
    timing = _timing_split(wall, compile_s, setup_s)
    results = [
        {"rounds_to_eps": hit[i],
         "final_grad": hist[i][-1][1] if hist[i] else float("nan"),
         "history": hist[i]}
        for i in range(B)
    ]
    if return_trajs:
        if pad:
            trajs = jax.tree.map(lambda x: x[:B], trajs)
        return (results, timing), trajs
    return results, timing


def cell_comm(p0: Dict[str, Any]):
    """The analytic per-round communication of a cell's static lowering
    (``repro.obs.ledger``) — the quadratic workload's packed dims are the
    problem geometry (DX, DY)."""
    from repro import obs

    p0 = _full_point(p0)
    return obs.round_comm(
        mixing_impl=p0["mixing_impl"], n=p0["n"], dims=(DX, DY),
        topology=p0["topology"],
        track=p0["algorithm"] in ("kgt_minimax", "gt_gda"),
        gossip_compress=p0["gossip_compress"])


def run_sweep(spec: grid_lib.GridSpec, *, mesh=None, store: bool = True,
              store_dir: Optional[str] = None, csv=None,
              telemetry=None, cache=cache_lib.UNSET) -> dict:
    """Run every static cell of ``spec`` batched; persist and return
    ``{"points": {point_key: {...}}, "cells": {cell_key: {...}}}``.

    Each cell record carries, alongside the compile/run timing split, a
    ``comm`` block — the communication ledger's analytic bytes/round for
    the cell's lowering and the total bytes its trajectories moved — so the
    stored sweep answers the paper's communication-efficiency question
    directly.  ``telemetry`` (a ``repro.obs.Telemetry``) additionally gets
    a per-cell span and ledger event, plus the cache's ``compile_cache.*``
    counters when the persistent compile cache is active; the cache's
    stats snapshot is stamped into the stored sweep's provenance.
    """
    from repro import obs

    tel = telemetry if telemetry is not None else obs.NULL
    cache = cache_lib.resolve(cache, telemetry)
    out: dict = {"name": spec.name, "points": {}, "cells": {}}
    for cell in spec.cells():
        with tel.span("cell", sweep=spec.name, cell=cell.key,
                      points=len(cell.points)):
            results, timing = run_cell(cell, mesh=mesh, cache=cache,
                                       telemetry=telemetry)
        ledger = obs.CommLedger(cell_comm(cell.points[0]))
        # rounds actually executed: each trajectory ran to its last
        # evaluation boundary (hit or max_rounds)
        cell_rounds = sum(res["history"][-1][0] if res["history"] else 0
                          for res in results)
        ledger.add_rounds(cell_rounds)
        tel.emit(ledger.event(rounds=cell_rounds, sweep=spec.name,
                              cell=cell.key))
        out["cells"][cell.key] = {
            "static": cell.static, "num_trajectories": len(cell.points),
            **timing,
            "comm": {**ledger.describe(), "rounds": cell_rounds,
                     "bytes_total": ledger.total_bytes}}
        if csv is not None:
            csv(f"sweep,{spec.name},cell={cell.key},B={len(cell.points)},"
                f"compile_s={timing['compile_s']},run_s={timing['run_s']},"
                f"comm_bytes_per_round={ledger.bytes_per_round}")
        for p, res in zip(cell.points, results):
            out["points"][grid_lib.point_key(p)] = {
                "params": dict(p), "cell": cell.key, **res}
    if cache is not None:
        out["compile_cache"] = cache.describe()
    if store:
        path = store_lib.save(
            spec.name, out, spec, directory=store_dir,
            extra_provenance=(
                {"compile_cache": cache.describe()} if cache is not None
                else None))
        out["store_path"] = path
    return out


def points_where(result: dict, **params) -> List[dict]:
    """Stored/returned points whose params match ``params`` (sweep order)."""
    return [rec for rec in result["points"].values()
            if all(rec["params"].get(k) == v for k, v in params.items())]


def summarize(points: List[dict]) -> dict:
    """mean±std over a replicate group (seeds): final grad + rounds-to-ε
    over the converged subset, plus the hit rate."""
    finals = [p["final_grad"] for p in points]
    hits = [p["rounds_to_eps"] for p in points if p["rounds_to_eps"] is not None]
    out = {
        "num": len(points),
        "final_grad_mean": float(np.mean(finals)) if finals else None,
        "final_grad_std": float(np.std(finals)) if finals else None,
        "hit_rate": len(hits) / len(points) if points else None,
    }
    if hits:
        out["rounds_to_eps_mean"] = float(np.mean(hits))
        out["rounds_to_eps_std"] = float(np.std(hits))
    else:
        out["rounds_to_eps_mean"] = None
        out["rounds_to_eps_std"] = None
    return out


def main() -> None:
    import argparse

    from repro.sweep import defs

    ap = argparse.ArgumentParser(
        description="Run named experiment sweeps as batched compiled cells")
    ap.add_argument("names", nargs="*", help="sweep names (see --list)")
    ap.add_argument("--list", action="store_true", help="list known sweeps")
    ap.add_argument("--out", default=None, help="store directory "
                    "(default: <repo>/results/sweeps)")
    ap.add_argument("--cache-dir", default=None, help="persistent compile "
                    "cache root (default: $REPRO_COMPILE_CACHE, else "
                    "<repo>/results/.xla_cache)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the persistent compile cache")
    args = ap.parse_args()
    if args.list or not args.names:
        for name, spec in sorted(defs.SWEEPS.items()):
            cells = spec.cells()
            npts = sum(len(c.points) for c in cells)
            print(f"{name}: {npts} points in {len(cells)} cells")
        return
    if args.no_cache:
        cache = None
    elif args.cache_dir is not None:
        cache_lib.enable_xla_cache(os.path.join(args.cache_dir, "xla"))
        cache = cache_lib.CompileCache(os.path.join(args.cache_dir, "aot"))
    else:
        # CLI default is cache ON unless the env says otherwise
        cache = cache_lib.from_env()
        if cache is None and os.environ.get(cache_lib.ENV_CACHE) is None:
            cache_lib.enable_xla_cache()
            cache = cache_lib.CompileCache()
    for name in args.names:
        spec = defs.SWEEPS[name]
        t0 = time.perf_counter()
        res = run_sweep(spec, store_dir=args.out, csv=print, cache=cache)
        print(f"sweep,{name},points={len(res['points'])},"
              f"cells={len(res['cells'])},wall_s={time.perf_counter()-t0:.1f},"
              f"store={res.get('store_path')}")
        if cache is not None:
            s = cache.stats
            print(f"sweep,{name},cache_hits={int(s['hits'])},"
                  f"cache_misses={int(s['misses'])},"
                  f"cache_memo_hits={int(s['memo_hits'])},"
                  f"cache_errors={int(s['errors'])},"
                  f"cache_bytes_written={int(s['bytes_written'])}")


if __name__ == "__main__":
    main()
