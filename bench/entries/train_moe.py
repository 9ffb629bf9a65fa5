"""Entry: the DRO training job on one chip's share of a sparse-expert model
through ``repro.launch.train.train()``, as ``entries/train.py`` runs the
dense one: the same chunk-boundary hook and window (imported from it), the
program's arch checked against the configuration (expert share, top-k,
multipliers), and ``reference/dro_moe.py`` in the reference's place.

The hook also keeps the routing counters of the chunks in the window
(``moe_rows_held``, ``moe_load_max`` of each logged round), which the
per-layer metrics read as rows per round.
"""
from __future__ import annotations

import os
import time

import numpy as np

import run as bench_run

base = bench_run.load_module(
    os.path.join(bench_run.BENCH, "entries", "train.py"), "bench_entry_train")

#: Configuration keys → the program's ``ModelConfig`` (``moe.*``: its
#: ``MoEConfig``).
MOE_KEYS = {"num_experts": "moe.num_experts", "experts_held": "moe.held",
            "top_k": "moe.top_k", "expert_d_ff": "moe.expert_d_ff",
            "router_aux_coef": "moe.router_aux_coef",
            "embedding_multiplier": "embedding_multiplier",
            "attention_multiplier": "attention_multiplier",
            "residual_multiplier": "residual_multiplier",
            "logits_scaling": "logits_scaling"}


def program_config(config: dict, argv):
    from repro.configs import registry

    cfg = registry.get_model_config(config["arch"])
    return registry.reduced(cfg) if "--reduced" in argv else cfg


def check_program_matches(config: dict, argv) -> None:
    """The dense shape as ``entries/train.py`` checks it, then the expert
    share and Granite's multipliers."""
    base.check_program_matches(config, argv)
    cfg = program_config(config, argv)
    got = {}
    for key, attr in MOE_KEYS.items():
        obj = cfg
        for part in attr.split("."):
            obj = getattr(obj, part)
        got[key] = obj
    want = {k: config[k] for k in MOE_KEYS}
    if got != want or cfg.moe.dispatch != "sorted":
        raise SystemExit(f"the program's {config['arch']} is {got} "
                         f"(dispatch {cfg.moe.dispatch!r}), the "
                         f"configuration {want} (dispatch 'sorted')")


class Hook(base.Hook):
    """``entries/train.py``'s hook, keeping the window's routing counters."""

    def __init__(self, run, checked_steps: int):
        super().__init__(run, checked_steps)
        self.window_rows = []
        self.window_load = []

    def __call__(self, state, records, prev_round):
        if self.run.t_open is not None and self.run.t_close is None:
            self.window_rows.extend(r["moe_rows_held"] for r in records)
            self.window_load.extend(r["moe_load_max"] for r in records)
        return super().__call__(state, records, prev_round)


def run(run) -> dict:
    from repro.launch import train as train_lib

    tr = run.traffic
    check_program_matches(run.config, tr["argv"])
    job = base.job_params(tr["argv"])
    argv = list(tr["argv"]) + ["--rounds", str(tr["rounds"]),
                               "--seed", str(run.seed)]
    args = train_lib.build_parser().parse_args(argv)
    hook = Hook(run, tr["checked_steps"])
    t_train = time.perf_counter()
    try:
        train_lib.train(args, hooks=[hook])
    except base.WindowClosed:
        pass
    if hook._chunk_span is not None:
        hook._chunk_span.__exit__(None, None, None)
    if run.t_close is None:
        raise SystemExit("train() returned before the window closed")
    tokens = hook.rounds * base.tokens_per_round(job)
    run.notes["set-up split"] = (
        f"imports and device {t_train - run.t_start_process:.3f} s; "
        f"train() to the end of chunk 1 {hook.t_first - t_train:.3f} s, "
        f"of which chunk compile {hook.compile_s} s; checked chunks 2-"
        f"{tr['checked_steps']} and their norms "
        f"{run.t_open - hook.t_first:.3f} s")
    run.notes["rounds in the window"] = hook.rounds
    rows = float(np.mean(hook.window_rows)) if hook.window_rows else None
    load = float(np.max(hook.window_load)) if hook.window_load else None
    run.notes["moe_rows_held per logged round in the window"] = rows
    run.notes["moe_load_max, largest in the window"] = load
    run.counts = {"rounds": hook.rounds, "tokens": tokens,
                  "chunks": hook.rounds // int(job["chunk"]),
                  "moe_rows_held_per_round": rows}
    return {"metrics": {"tokens_per_s_per_chip":
                        tokens / run.window_s / run.spec.chips},
            "attempted": hook.rounds,
            "failed": int(hook.nonfinite),
            "capture": {"rows": hook.rows, "corr_norms": hook.corr_norms,
                        "change_norms": hook.change_norms}}


def reference_job(run, numerics):
    from reference import dro_moe
    from reference.topology import mixing_matrix

    a = base.job_params(run.traffic["argv"])
    n = int(a["clients"])
    job = {"clients": n, "local_steps": int(a["local_steps"]),
           "batch": int(a["batch"]), "seq_len": int(a["seq_len"]),
           "groups": int(a["groups"]), "mu": float(a["mu"]),
           "alpha": float(a["alpha"]),
           "eta": {"cx": float(a["eta_cx"]), "cy": float(a["eta_cy"]),
                   "s": float(a["eta_s"])},
           "mixing_matrix": mixing_matrix(a["topology"], n)}
    return dro_moe.Job(run.config, job, numerics)


def follow(run, numerics) -> dict:
    a = base.job_params(run.traffic["argv"])
    return reference_job(run, numerics).follow(
        run.seed, steps=run.traffic["checked_steps"],
        rounds_per_step=int(a["chunk"]), log_every=int(a["log_every"]))


def program_params(ref_params: dict):
    """The reference's flat leaves as the program's parameter tree."""
    layer = {}
    for name, leaf in ref_params.items():
        if name in ("embed", "final_norm"):
            continue
        *outer, inner = name.split(".")
        (layer.setdefault(outer[0], {}) if outer else layer)[inner] = leaf
    return {"embed": ref_params["embed"], "stack": ((layer,),),
            "final_norm": ref_params["final_norm"]}


def routing_flip_share(run) -> float:
    """Share of (client, layer, token) of round 0's first local step whose
    set of held experts differs between the program's model (its own
    code, at its own precision) and the reference, from the same weights
    and tokens.  Routing is discrete: a rounding can flip a token's k-th
    pick, which the leaf gaps then carry."""
    import jax

    from reference.dro_lm import Numerics
    from repro.models import model as model_lib

    job = reference_job(run, Numerics())
    x0, batches, ref_held = job.first_step_held(run.seed)
    cfg = program_config(run.config, run.traffic["argv"])
    groups = int(base.job_params(run.traffic["argv"])["groups"])
    # the weights are an argument: closed over, they would be compiled in
    prog = jax.jit(jax.vmap(lambda p, b: model_lib.per_group_loss(
        p, b, cfg, num_groups=groups)[2]["held"], in_axes=(None, 0)))(
            program_params(x0), batches)
    return float(np.mean(np.any(np.asarray(prog) != ref_held, axis=-1)))


def check(run, result) -> dict:
    """Every number of ``entries/train.py``'s ``compare`` with the cell's
    limit, and the routing flips (not compared)."""
    from reference.dro_lm import Numerics

    t = time.perf_counter()
    ref = follow(run, Numerics())
    run.notes["reference seconds"] = time.perf_counter() - t
    nums = base.compare(result["capture"], ref, run.traffic)
    nums["routing_flip_share"] = routing_flip_share(run)
    limits = run.spec.cell["limits"]
    return {k: {"value": v, "limit": limits.get(k)} for k, v in nums.items()}


def control_readings(spec, seed: int) -> dict:
    """``compare``'s numbers for the reference computed with matrix
    operands in the configuration's ``control_precision``."""
    from types import SimpleNamespace

    from reference.dro_lm import Numerics

    run = SimpleNamespace(traffic=spec.traffic, config=spec.config,
                          seed=seed)
    ref = follow(run, Numerics())
    low = follow(run, Numerics(spec.config["control_precision"]))
    return base.compare(low, ref, spec.traffic)
