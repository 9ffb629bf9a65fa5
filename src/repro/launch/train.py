"""Decentralized K-GT-Minimax training driver.

Runs real federated minimax training (DRO over the selected architecture)
with the full substrate: heterogeneous synthetic data, round batching,
schedules, checkpointing, and streaming diagnostics.  On this CPU container
it trains reduced configs / paper-toy end-to-end; on a TPU cluster the same
driver lowers onto the decentralized mesh via ``--mesh decentralized``.

Execution is delegated to ``repro.engine`` (``--engine scan``, the
default): R-round chunks compile as a single ``lax.scan`` program with
device-side data sampling and an on-device metrics buffer, so the host
pays one dispatch + one metrics read per chunk instead of per round.
``--engine host`` keeps the historical per-round loop (same sampler, same
metrics — the trajectories are bit-identical, see tests/test_engine.py)
for A/B and debugging.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch paper-toy --rounds 50
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --rounds 20 --clients 4 --local-steps 4 --algorithm local_sgda
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import engine as engine_lib
from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import registry
from repro.configs.base import AlgorithmConfig, MinimaxConfig
from repro.core import adversary as adversary_lib
from repro.core import kgt_minimax as kgt
from repro.core import mixing as mixing_lib
from repro.core import objectives, topology
from repro.core import sparse_topology as sparse_lib
from repro.core import stochastic_topology as stoch_lib
from repro.data import synthetic as data_lib
from repro.optim import schedules


# (key, format) pairs rendered when present: a metrics schema without
# f_bar/mean_loss (e.g. quadratic_metrics_fn rows) must not KeyError the
# console stream — format only the keys the row actually carries.
_RECORD_FORMATS = (
    ("f_bar", "f(x̄,ȳ)={:.4f}"),
    ("phi_grad_norm", "‖∇Φ‖={:.4f}"),
    ("mean_loss", "ℓ̄={:.4f}"),
    ("eval_loss", "ℓ_eval={:.4f}"),
    ("consensus_x", "Ξx={:.3e}"),
    ("y_bar_norm", "|ȳ|={:.3f}"),
)


def _format_record(rec: dict) -> str:
    parts = []
    if "round" in rec:
        parts.append(f"round {int(rec['round']):4d}")
    for key, fmt in _RECORD_FORMATS:
        if key in rec:
            parts.append(fmt.format(rec[key]))
    parts.append(f"({rec.get('wall_s', 0)}s)")
    return "[train] " + "  ".join(parts)


def _print_record(rec: dict) -> None:
    print(_format_record(rec), flush=True)


def _stderr_event_format(event: dict):
    """The console view of the telemetry stream: metric rows render exactly
    as the historical print logging; everything else stays JSONL-only."""
    if event.get("type") != "metrics":
        return None
    return _format_record(
        {k: v for k, v in event.items() if k not in ("v", "type", "t")})


def _build_telemetry(args, algo, cfg, state):
    """(telemetry, ledger, profiler) from the CLI flags.

    The stderr sink is always on (it *is* the historical console logging);
    the JSONL sink, the communication ledger, and the health gauges arm
    only with ``--telemetry-out``, and the profiler only with
    ``--profile-dir`` — so a plain run does no extra device work
    (tests/test_obs.py pins the bit-identity of the trajectory).
    """
    from repro import obs

    tel_path = getattr(args, "telemetry_out", None)
    sinks = [obs.StderrSink(_stderr_event_format)]
    ledger = None
    if tel_path:
        sinks.append(obs.JsonlSink(tel_path))
        ledger = obs.ledger_for_state(algo, state)
    telemetry = obs.Telemetry(sinks)
    profile_dir = getattr(args, "profile_dir", None)
    profiler = (obs.Profiler(profile_dir,
                             num_rounds=getattr(args, "profile_rounds", 0))
                if profile_dir else None)
    if tel_path:
        telemetry.meta(
            "train", arch=cfg.name, algorithm=algo.algorithm,
            n=algo.num_clients, local_steps=algo.local_steps,
            topology=algo.topology, mixing_impl=algo.mixing_impl,
            gossip_dtype=algo.gossip_dtype,
            gossip_compress=algo.gossip_compress,
            num_byzantine=algo.num_byzantine, attack=algo.attack,
            participation=algo.participation_rate,
            rounds=args.rounds, seed=args.seed,
            ledger=ledger.describe())
    return telemetry, ledger, profiler


def _compile_cache(args):
    """Resolve ``--compile-cache`` / ``$REPRO_COMPILE_CACHE`` (flag wins)
    into a ``repro.sweep.cache.CompileCache``, or None when neither is
    given or it is ``off``.  ``on`` turns on both layers at their default
    places; a directory roots both there."""
    from repro.sweep import cache as cache_lib

    spec = getattr(args, "compile_cache", None)
    if spec is None:
        spec = os.environ.get(cache_lib.ENV_CACHE)
    if spec is None:
        return None
    s = str(spec).strip().lower()
    if s in cache_lib._OFF_VALUES:
        return None
    if s in cache_lib._ON_VALUES:
        cache_lib.enable_xla_cache()
        return cache_lib.CompileCache()
    cache_lib.enable_xla_cache(os.path.join(str(spec), "xla"))
    return cache_lib.CompileCache(os.path.join(str(spec), "aot"))


def _train_statics(args) -> tuple:
    """The cache-key statics of the train chunk program: every CLI argument
    that can reach the traced program or its *baked* constants (the data
    model, sampler keys, and schedule are closure constants derived from
    these — see the warning in ``repro.sweep.cache``).  Only output-path
    arguments are excluded."""
    skip = {"out", "telemetry_out", "profile_dir", "profile_rounds",
            "checkpoint_dir", "checkpoint_every", "compile_cache"}
    return tuple(sorted((k, repr(v)) for k, v in vars(args).items()
                        if k not in skip))


def _build_mesh_programs(args, cfg, algo, minimax, sched, sampler, metrics_fn,
                         engine_mode):
    """The repro.dist-sharded program over the local device mesh: the chunk
    builder (scan engine) or the per-round step (host engine) — only the
    one the selected engine runs — plus the state's sharding and that of a
    per-client array."""
    import math

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import InputShape, MeshConfig
    from repro.dist import compat
    from repro.dist.sharding import CLIENTS
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps as steps_lib

    # clients axis must divide the state's leading dim (= num_clients):
    # use the largest device count that does.
    n_dev = math.gcd(len(jax.devices()), algo.num_clients)
    mesh = mesh_lib.local_mesh(n_dev)
    mcfg = MeshConfig(num_clients=algo.num_clients, fsdp=1, model=1,
                      param_mode="replicated", remat=False)
    shape = InputShape(name="train_cli", seq_len=args.seq_len,
                       global_batch=args.batch * algo.num_clients,
                       kind="train")
    # the leading (clients) dim of a per-client array on the clients axis
    client_shard = NamedSharding(mesh, P(CLIENTS))
    with compat.use_mesh(mesh):
        if engine_mode == "scan":
            build_chunk, _, state_shard = steps_lib.build_train_chunk(
                cfg, shape, mesh, mcfg, algo=algo, minimax=minimax,
                lr_scale=sched, sampler=sampler, metrics_fn=metrics_fn,
                log_every=args.log_every)
            return None, build_chunk, state_shard, client_shard
        step, _, _, _, (state_shard, _, _) = steps_lib.build_train_round(
            cfg, shape, mesh, mcfg, algo=algo, minimax=minimax,
            lr_scale=sched)
    return step, None, state_shard, client_shard


def train(args, hooks=()) -> dict:
    """Run the job ``args`` describes (the CLI's namespace) and return its
    JSON-able result.  ``hooks`` are extra chunk-boundary hooks of the scan
    engine, ``hook(state, records, prev_round)`` (``repro.engine.run``)."""
    if hooks and getattr(args, "engine", "scan") != "scan":
        raise ValueError("train(hooks=...) needs --engine scan")
    # before the first compile, so every program of the run can be cached
    cache = _compile_cache(args)
    cfg = registry.get_model_config(args.arch)
    if args.reduced:
        cfg = registry.reduced(cfg)
    algo = AlgorithmConfig(
        algorithm=args.algorithm,
        num_clients=args.clients,
        local_steps=args.local_steps,
        eta_cx=args.eta_cx,
        eta_cy=args.eta_cy,
        eta_sx=args.eta_s,
        eta_sy=args.eta_s,
        topology=args.topology,
        mixing_impl=args.mixing_impl,
        gossip_dtype=args.gossip_dtype,
        # getattr: programmatic callers (tests) build a bare Namespace
        gossip_backend=getattr(args, "gossip_backend", "auto"),
        gossip_compress=(None if getattr(args, "gossip_compress", None)
                         in (None, "none") else args.gossip_compress),
        topology_family=getattr(args, "topology_family", "static"),
        edge_prob=getattr(args, "edge_prob", 0.5),
        client_drop_prob=getattr(args, "client_drop_prob", 0.3),
        participation_rate=getattr(args, "participation", 1.0),
        topology_seed=(getattr(args, "topology_seed", None)
                       if getattr(args, "topology_seed", None) is not None
                       else args.seed),
        num_byzantine=getattr(args, "num_byzantine", 0),
        attack=getattr(args, "attack", "sign_flip"),
        attack_scale=getattr(args, "attack_scale", 1.0),
        robust_trim=getattr(args, "robust_trim", 1),
    )
    random_w = algo.topology_family != "static"
    part = algo.participation_rate < 1.0
    byz = algo.num_byzantine > 0
    minimax = MinimaxConfig(num_groups=args.groups, mu=args.mu)
    engine_mode = getattr(args, "engine", "scan")
    chunk_rounds = max(1, min(int(getattr(args, "chunk", 16)),
                              max(args.rounds, 1)))
    mesh_mode = getattr(args, "mesh", "host")

    key = jax.random.PRNGKey(args.seed)
    kd, ki, kt = jax.random.split(key, 3)

    dm = data_lib.make_data_model(
        kd, vocab_size=cfg.vocab_size, num_groups=args.groups,
        num_clients=algo.num_clients, alpha=args.alpha)
    problem = objectives.dro_problem(
        cfg, num_groups=args.groups, mu=args.mu, remat=False)

    init_b = jax.tree.map(
        lambda x: x[0],
        data_lib.round_batches(
            dm, jax.random.fold_in(kd, 1), local_steps=1,
            num_clients=algo.num_clients, per_client_batch=args.batch,
            seq_len=args.seq_len, cfg=cfg))

    def init_fn(k, b):
        return kgt.init_state(problem, algo, k, init_batch=b,
                              init_keys=jax.random.split(k, algo.num_clients))

    sched = schedules.get_schedule(args.schedule, args.rounds, args.warmup)

    # Device-side data path: the per-round sampler (a pure function of the
    # round index, callable inside the scanned chunk) and one fixed held-out
    # eval batch — logged train metrics use the round's own data, eval
    # metrics use data the optimizer never sees.
    sampler = engine_lib.make_dro_sampler(
        dm, kt, local_steps=algo.local_steps, num_clients=algo.num_clients,
        per_client_batch=args.batch, seq_len=args.seq_len, cfg=cfg)
    if random_w or part or byz:
        # churn + adversary axes ride the sampler slot: per-round W /
        # participation mask / attack drawn on device from the round index
        # (checkpoint-restore exact)
        if mesh_mode == "decentralized":
            raise ValueError(
                "--topology-family/--participation/--num-byzantine are not "
                "supported with --mesh decentralized yet (the sharded chunk "
                "builder bakes a static W); run on the host mesh")
        topo_key = jax.random.PRNGKey(algo.topology_seed)
        w_fn = None
        if random_w:
            if algo.mixing_impl.startswith("sparse_"):
                # the sampled W rides the extras slot as a SparseTopology
                # pytree drawn on the support graph's neighbor lists —
                # no (n, n) array anywhere on the churn path
                support = sparse_lib.sparse_mixing_matrix(
                    algo.topology, algo.num_clients)
                w_fn = sparse_lib.make_sparse_w_sampler(
                    algo.topology_family, support, topo_key,
                    edge_prob=algo.edge_prob,
                    client_drop_prob=algo.client_drop_prob)
            else:
                base_w = (topology.mixing_matrix(algo.topology,
                                                 algo.num_clients)
                          if algo.topology_family == "dropout" else None)
                w_fn = stoch_lib.make_w_sampler(
                    algo.topology_family, algo.num_clients, topo_key,
                    base_w=base_w, edge_prob=algo.edge_prob,
                    client_drop_prob=algo.client_drop_prob)
        mask_fn = None
        if part:
            mask_fn = stoch_lib.make_participation_sampler(
                algo.num_clients, topo_key, algo.participation_rate)
        attack_fn = None
        if byz:
            attack_fn = adversary_lib.make_attack_sampler(
                algo.num_clients, topo_key,
                num_byzantine=algo.num_byzantine, attack=algo.attack,
                scale=algo.attack_scale)
        sampler = engine_lib.with_topology(
            sampler, w_fn=w_fn, mask_fn=mask_fn, attack_fn=attack_fn)
    eval_b = engine_lib.held_out_eval_batch(
        dm, jax.random.fold_in(kd, 2), num_clients=algo.num_clients,
        per_client_batch=args.batch, seq_len=args.seq_len, cfg=cfg)
    metrics_fn = engine_lib.dro_metrics_fn(
        problem, cfg, num_groups=args.groups, eval_batch=eval_b)

    if mesh_mode == "decentralized":
        # Sharded path: the same jit programs the dry-run lowers for a pod,
        # here over whatever local devices exist (clients axis = n_devices).
        # repro.dist places the leading clients dim of the K-GT-Minimax
        # state on the "clients" mesh axis; only gossip crosses clients.
        # The state is born sharded: each device computes and holds only
        # its own clients' rows, never the whole client stack.
        step, build_chunk, state_shard, client_shard = _build_mesh_programs(
            args, cfg, algo, minimax, sched, sampler, metrics_fn, engine_mode)
        state = jax.jit(init_fn, out_shardings=state_shard)(
            ki, jax.device_put(init_b, client_shard))
    else:
        # one compiled program: eager op-by-op dispatch of the init
        # gradients is slow at full width on an accelerator
        state = jax.jit(init_fn)(ki, init_b)
        round_step = kgt.make_round_step(problem, algo, lr_scale=sched,
                                         traced_w=random_w,
                                         participation=part,
                                         byzantine=byz)
        step = jax.jit(round_step)
        build_chunk = engine_lib.make_chunk_builder(
            round_step, sampler, metrics_fn, log_every=args.log_every)
        if cache is not None and engine_mode == "scan":
            # the AOT layer applies only on the host path: the sharded mesh
            # programs embed their device assignment (layer 1 — jax's own
            # persistent cache — still covers them via _compile_cache above)
            build_chunk = engine_lib.timed_chunk_builder(
                build_chunk, cache=cache, statics=_train_statics(args))
    if random_w:
        # W is redrawn every round: a static spectral gap would mislabel
        # the run, so report the family (and its rate) instead
        topo_part = (f"family={algo.topology_family}"
                     + (f" (edge_prob={algo.edge_prob})"
                        if algo.topology_family == "erdos_renyi" else "")
                     + (f" (drop={algo.client_drop_prob})"
                        if algo.topology_family == "dropout" else ""))
    elif (algo.mixing_impl.startswith("sparse_")
          and algo.num_clients > stoch_lib.DENSE_MATERIALIZATION_LIMIT):
        # densifying just to report an eigengap defeats the sparse path
        support = sparse_lib.sparse_mixing_matrix(
            algo.topology, algo.num_clients)
        topo_part = (f"{algo.topology} (sparse, "
                     f"max_deg={support.max_degree})")
    else:
        w = topology.mixing_matrix(algo.topology, algo.num_clients)
        topo_part = f"p={topology.spectral_gap(w):.3f}"
    if part:
        topo_part += f", participation={algo.participation_rate}"
    if byz:
        topo_part += (f", byzantine={algo.num_byzantine} "
                      f"({algo.attack} x{algo.attack_scale})")
    print(f"[train] {cfg.name}: {sum(x.size for x in jax.tree.leaves(state.x))/1e6:.2f}M "
          f"client-stacked params, n={algo.num_clients}, K={algo.local_steps}, "
          f"{topo_part}, algo={algo.algorithm}, "
          f"engine={engine_mode}"
          + (f" (chunk={chunk_rounds})" if engine_mode == "scan" else ""),
          flush=True)

    telemetry, ledger, profiler = _build_telemetry(args, algo, cfg, state)
    try:
        if engine_mode == "scan":
            from repro import obs

            # the telemetry hook routes metric rows to the stderr sink
            # (the historical console log) and, with --telemetry-out, the
            # ledger + health gauges into the JSONL stream
            engine_hooks = [engine_lib.telemetry_hook(
                telemetry, ledger=ledger,
                health_fn=obs.health_gauges if ledger is not None else None)]
            if args.checkpoint_every:
                engine_hooks.append(engine_lib.checkpoint_hook(
                    args.checkpoint_dir, args.checkpoint_every,
                    metadata={"arch": cfg.name}, verbose=True))
            if profiler is not None:
                profiler.start()
                engine_hooks.append(profiler.hook)
            engine_hooks.extend(hooks)

            state, history = engine_lib.run(
                state, build_chunk, total_rounds=args.rounds,
                chunk_rounds=chunk_rounds, hooks=engine_hooks,
                # chunk boundaries land on every checkpoint multiple, so the
                # requested cadence is honored exactly (matches --engine host)
                boundary_every=args.checkpoint_every or None,
                telemetry=telemetry if ledger is not None else None)
        else:
            history = _host_loop(args, state, step, sampler, metrics_fn, cfg,
                                 telemetry=telemetry, ledger=ledger)
    finally:
        if profiler is not None:
            profiler.stop()
        telemetry.close()

    return {
        "history": history,
        "final_consensus": history[-1]["consensus_x"] if history else None,
    }


def _host_loop(args, state, step, sampler, metrics_fn, cfg,
               telemetry=None, ledger=None):
    """The historical per-round loop (``--engine host``): per-round jit
    dispatch with eagerly sampled batches.  Kept as the A/B reference — it
    runs the same sampler and metrics as the scan engine, so trajectories
    and logged diagnostics are identical, just slower.  Metric rows flow
    through the telemetry stream (the stderr sink renders the historical
    console line); the ledger accumulates per logged interval."""
    sample = jax.jit(sampler)
    metrics = jax.jit(metrics_fn)
    history = []
    # monotonic clock: wall_s stamps must never go backwards mid-run
    # (wall-clock deltas can, under NTP slew) — matches engine.py
    t0 = time.perf_counter()
    prev_logged = 0
    for t in range(args.rounds):
        batches, keys, extras = engine_lib.split_sampled(sample(jnp.int32(t)))
        state = step(state, batches, keys, *extras)

        if t % args.log_every == 0 or t == args.rounds - 1:
            rec = engine_lib.row_to_record(
                jax.device_get(metrics(state, batches)), t)
            rec["wall_s"] = round(time.perf_counter() - t0, 3)
            history.append(rec)
            if telemetry is not None:
                telemetry.metrics(rec)
            else:
                _print_record(rec)
            if ledger is not None:
                ledger.add_rounds(t + 1 - prev_logged)
                telemetry.emit(ledger.event(rounds=t + 1 - prev_logged,
                                            round=t + 1))
                prev_logged = t + 1

        if args.checkpoint_every and (t + 1) % args.checkpoint_every == 0:
            path = os.path.join(args.checkpoint_dir, f"round_{t+1:06d}.npz")
            ckpt_lib.save(path, state, metadata={"round": t + 1, "arch": cfg.name})
            print(f"[train] checkpoint -> {path}", flush=True)
    return history


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-toy")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized variant of the arch")
    ap.add_argument("--algorithm", default="kgt_minimax",
                    choices=["kgt_minimax", "dsgda", "local_sgda", "gt_gda"])
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--mu", type=float, default=1.0)
    ap.add_argument("--alpha", type=float, default=0.3, help="Dirichlet heterogeneity")
    ap.add_argument("--eta-cx", type=float, default=0.05)
    ap.add_argument("--eta-cy", type=float, default=0.5)
    ap.add_argument("--eta-s", type=float, default=0.7)
    ap.add_argument("--engine", default="scan", choices=["scan", "host"],
                    help="scan: repro.engine chunked lax.scan over rounds "
                         "with on-device sampling/metrics; host: per-round "
                         "dispatch (A/B fallback, bit-identical trajectory)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="rounds per compiled scan chunk (--engine scan)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "decentralized"],
                    help="host: plain single-device jit; decentralized: the "
                         "repro.dist-sharded round over the local device mesh")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--topology-family", default="static",
                    choices=list(stoch_lib.TOPOLOGY_FAMILIES),
                    help="per-round random topology (repro.core."
                         "stochastic_topology): static keeps --topology "
                         "fixed; erdos_renyi draws G(n, --edge-prob) with "
                         "Metropolis weights; pairwise averages one random "
                         "pair per round; dropout drops each client's links "
                         "with --client-drop-prob (self-loop fallback)")
    ap.add_argument("--edge-prob", type=float, default=0.5,
                    help="erdos_renyi: per-round link probability")
    ap.add_argument("--client-drop-prob", type=float, default=0.3,
                    help="dropout family: per-round P[client drops links]")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="partial participation: per-round P[client active]; "
                         "< 1 freezes inactive clients' (theta, c) for the "
                         "round (Bernoulli mask, self-loop fallback)")
    ap.add_argument("--topology-seed", type=int, default=None,
                    help="seed of the W/mask/attack sampling streams "
                         "(default: --seed)")
    ap.add_argument("--num-byzantine", type=int, default=0,
                    help="Byzantine clients (ids 0..f-1): their outgoing "
                         "round deltas are replaced per --attack before "
                         "gossip (repro.core.adversary); pair with a robust "
                         "--mixing-impl (coord_median / trimmed_mean) to "
                         "tolerate them")
    ap.add_argument("--attack", default="sign_flip",
                    choices=list(adversary_lib.ATTACKS),
                    help="Byzantine attack model applied to attackers' "
                         "outgoing deltas")
    ap.add_argument("--attack-scale", type=float, default=1.0,
                    help="attack magnitude multiplier")
    ap.add_argument("--robust-trim", type=int, default=1,
                    help="trimmed_mean: neighbor values trimmed per side "
                         "per coordinate")
    from repro.kernels.ops import GOSSIP_BACKENDS

    ap.add_argument("--mixing-impl", default="dense",
                    choices=list(mixing_lib.MIXING_IMPLS))
    ap.add_argument("--gossip-dtype", default="float32")
    from repro.core.compression import COMPRESS_METHODS

    ap.add_argument("--gossip-compress", default="none",
                    choices=["none", *COMPRESS_METHODS],
                    help="error-feedback quantized gossip: compress the "
                         "transmitted round delta (bf16 | int8) and carry "
                         "the quantization residual as per-client EF state; "
                         "requires a packed --mixing-impl (pallas_packed / "
                         "fused_round)")
    ap.add_argument("--gossip-backend", default="auto",
                    choices=list(GOSSIP_BACKENDS),
                    help="pallas_packed epilogue backend (auto: Pallas "
                         "kernel on TPU, packed-xla oracle elsewhere)")
    ap.add_argument("--schedule", default="constant")
    ap.add_argument("--warmup", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--telemetry-out", default=None,
                    help="write the structured telemetry stream (spans, "
                         "metric rows, communication ledger, health gauges) "
                         "as JSONL to this path; summarize it with "
                         "`python -m repro.obs.report <path>`")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler Perfetto trace into this "
                         "directory (open in Perfetto/TensorBoard)")
    ap.add_argument("--profile-rounds", type=int, default=0,
                    help="close the profiler capture window after this many "
                         "rounds (0 = profile the whole run)")
    ap.add_argument("--compile-cache", default=None, metavar="DIR|on|off",
                    help="persistent compile cache (repro.sweep.cache): a "
                         "directory roots both layers, 'on' uses the default "
                         "places, 'off' disables; default: "
                         "$REPRO_COMPILE_CACHE, else jax's cache alone at "
                         "$JAX_COMPILATION_CACHE_DIR or "
                         "results/.xla_cache/xla")
    ap.add_argument("--out", default=None)
    return ap


def main() -> None:
    from repro.sweep import cache as cache_lib

    args = build_parser().parse_args()
    if args.compile_cache is None and cache_lib.ENV_CACHE not in os.environ:
        # the CLI keeps jax's persistent cache on by default; train() itself
        # turns on only what it is asked for
        cache_lib.enable_xla_cache()
    result = train(args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
