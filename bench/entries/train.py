"""Entry: the DRO training job through ``repro.launch.train.train()``, as
its CLI runs it, with the benchmark's window as a chunk-boundary hook.

``train()`` compiles its scanned chunk (``--chunk`` rounds) on the first
call and then drives chunks until ``--rounds``, which the traffic file sets
far beyond the window.  The hook sees the state after every chunk:

* after chunks 1 to ``checked_steps`` (set-up) it keeps what the
  comparison reads: the logged metric rows, the per-leaf norms of the x
  correction after chunk 1, and the per-leaf norms of the change of x from
  chunk 1 to the last checked chunk (a device copy of x after chunk 1 is
  held until then; the state itself is donated to the next chunk);
* then it opens the window, and at the first boundary ``--seconds`` past
  the opening it closes it and stops ``train()`` by raising.

The time between hooks is one chunk's dispatch, execution and metrics
read-back, as ``repro.engine.run`` does them; a span ``bench.train.chunk``
covers it on the profiler's clock.
"""
from __future__ import annotations

import math
import time

import numpy as np


class WindowClosed(Exception):
    """Raised from the hook to end ``train()`` after the window."""


def leaf_name(path) -> str:
    """A state leaf's path → the reference's name (``attn.wq``, ``embed``)."""
    import jax

    keys = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    return ".".join(k for k in keys if k != "stack")


def named(tree) -> dict:
    import jax

    return {leaf_name(p): leaf
            for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def check_program_matches(config: dict, argv) -> None:
    """The registry's model (its smoke-test size under ``--reduced``) must
    be the configuration file's."""
    from repro.configs import registry

    cfg = registry.get_model_config(config["arch"])
    if "--reduced" in argv:
        cfg = registry.reduced(cfg)
    got = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
           "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
           "norm_eps": cfg.norm_eps, "tie_embeddings": cfg.tie_embeddings,
           "blocks": sorted(set(cfg.blocks()))}
    want = {k: config[k] for k in got}
    if got != want:
        raise SystemExit(f"the program's {config['arch']} is {got}, the "
                         f"configuration {want}")


class Hook:
    def __init__(self, run, checked_steps: int):
        self.run = run
        self.checked = checked_steps
        self.boundaries = 0
        self.rows = []
        self.corr_norms = None
        self.x_first = None
        self.change_norms = None
        self.round_open = None
        self.rounds = 0
        self.compile_s = None
        self.t_first = None
        self.nonfinite = False
        self._chunk_span = None


    def __call__(self, state, records, prev_round):
        import jax.numpy as jnp

        if self._chunk_span is not None:
            self._chunk_span.__exit__(None, None, None)
        self.boundaries += 1
        b = self.boundaries
        if b == 1:
            self.t_first = time.perf_counter()
            self.compile_s = records[0]["compile_s"] if records else None
        if b <= self.checked:
            self.rows.extend(records)
            if b == 1:
                self.corr_norms = {k: float(norm(v)) for k, v in
                                   named(state.cx).items()}
                self.x_first = {k: jnp.copy(v) for k, v in
                                named(state.x).items()}
            if b == self.checked:
                self.change_norms = {
                    k: float(norm(v - self.x_first[k]))
                    for k, v in named(state.x).items()}
                for v in self.x_first.values():
                    v.delete()
                self.x_first = None
                self.round_open = int(state.round)
                self.run.open_window()
        else:
            if not all(math.isfinite(v) for r in records
                       for v in _scalars(r)):
                self.nonfinite = True
            if self.run.elapsed() >= self.run.seconds:
                self.rounds = int(state.round) - self.round_open
                self.run.close_window()
                raise WindowClosed
        self._chunk_span = self.run.span("train.chunk")
        self._chunk_span.__enter__()


def norm(leaf):
    """‖leaf‖ in float32, reduced on the device."""
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))


def _scalars(rec):
    for v in rec.values():
        if isinstance(v, list):
            yield from v
        else:
            yield v


#: Trainer flags that take no value.
SWITCHES = ("--reduced",)


def job_params(argv) -> dict:
    """The traffic's ``--flag value`` pairs as a dict (every flag of the
    job is stated in the traffic file, so no default of the program's
    parser enters the reference)."""
    argv = [a for a in argv if a not in SWITCHES]
    if len(argv) % 2:
        raise ValueError(f"unpaired trainer arguments {argv}")
    return {argv[i].lstrip("-").replace("-", "_"): argv[i + 1]
            for i in range(0, len(argv), 2)}


def tokens_per_round(job: dict) -> int:
    return (int(job["clients"]) * int(job["local_steps"]) * int(job["batch"])
            * int(job["seq_len"]))


def run(run) -> dict:
    from repro.launch import train as train_lib

    tr = run.traffic
    check_program_matches(run.config, tr["argv"])
    job = job_params(tr["argv"])
    argv = list(tr["argv"]) + ["--rounds", str(tr["rounds"]),
                               "--seed", str(run.seed)]
    args = train_lib.build_parser().parse_args(argv)
    hook = Hook(run, tr["checked_steps"])
    t_train = time.perf_counter()
    try:
        train_lib.train(args, hooks=[hook])
    except WindowClosed:
        pass
    if hook._chunk_span is not None:
        hook._chunk_span.__exit__(None, None, None)
    if run.t_close is None:
        raise SystemExit("train() returned before the window closed")
    tokens = hook.rounds * tokens_per_round(job)
    chunk = int(job["chunk"])
    run.notes["set-up split"] = (
        f"imports and device {t_train - run.t_start_process:.3f} s; "
        f"train() to the end of chunk 1 {hook.t_first - t_train:.3f} s, "
        f"of which chunk compile {hook.compile_s} s; checked chunks 2-"
        f"{tr['checked_steps']} and their norms "
        f"{run.t_open - hook.t_first:.3f} s")
    run.notes["rounds in the window"] = hook.rounds
    run.counts = {"rounds": hook.rounds, "tokens": tokens,
                  "chunks": hook.rounds // chunk}
    return {"metrics": {"tokens_per_s_per_chip":
                        tokens / run.window_s / run.spec.chips},
            "attempted": hook.rounds,
            "failed": int(hook.nonfinite),
            "capture": {"rows": hook.rows, "corr_norms": hook.corr_norms,
                        "change_norms": hook.change_norms}}


def leaf_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's ``|‖p‖ − ‖r‖| / max(‖r‖, median leaf's ‖r‖)``, over the
    leaves the reference moves: a leaf whose reference norm is under a
    thousandth of the median leaf's is left out."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref
               if ref[k] >= 1e-3 * med)


def compare(capture: dict, ref: dict, traffic: dict) -> dict:
    """Relative gaps of the logged rows' quantities, grouped as the
    traffic's ``row_gaps`` names them (losses, consensus errors, ȳ,
    f(x̄,ȳ)), and the worst-leaf gaps.  The cell's limits say which of
    them are compared."""
    rows = {r["round"]: r for r in capture["rows"]}
    out = {}
    for name, keys in traffic["row_gaps"].items():
        worst = 0.0
        for rr in ref["rows"]:
            pr = rows.get(rr["round"])
            if pr is None:
                worst = math.inf
                continue
            for k in keys:
                gap = abs(pr[k] - rr[k]) / abs(rr[k])
                worst = max(worst, gap if math.isfinite(gap) else math.inf)
        out[name] = worst
    out["corr_leaf_gap"] = leaf_gap(capture["corr_norms"], ref["corr_norms"])
    out["change_leaf_gap"] = leaf_gap(capture["change_norms"],
                                      ref["change_norms"])
    return out


def reference_job(run, numerics):
    from reference import dro_lm
    from reference.topology import mixing_matrix

    a = job_params(run.traffic["argv"])
    n = int(a["clients"])
    job = {"clients": n, "local_steps": int(a["local_steps"]),
           "batch": int(a["batch"]), "seq_len": int(a["seq_len"]),
           "groups": int(a["groups"]), "mu": float(a["mu"]),
           "alpha": float(a["alpha"]),
           "eta": {"cx": float(a["eta_cx"]), "cy": float(a["eta_cy"]),
                   "s": float(a["eta_s"])},
           "mixing_matrix": mixing_matrix(a["topology"], n)}
    return dro_lm.Job(run.config, job, numerics)


def follow(run, numerics) -> dict:
    a = job_params(run.traffic["argv"])
    return reference_job(run, numerics).follow(
        run.seed, steps=run.traffic["checked_steps"], rounds_per_step=int(a["chunk"]),
        log_every=int(a["log_every"]))


def check(run, result) -> dict:
    """Every number of :func:`compare`, each with the cell's limit (None
    where the cell holds it to none)."""
    from reference.dro_lm import Numerics

    t = time.perf_counter()
    ref = follow(run, Numerics())
    run.notes["reference seconds"] = time.perf_counter() - t
    nums = compare(result["capture"], ref, run.traffic)
    limits = run.spec.cell["limits"]
    return {k: {"value": v, "limit": limits.get(k)} for k, v in nums.items()}


def control_readings(spec, seed: int) -> dict:
    """:func:`compare`'s numbers for the reference computed with matrix
    operands in the configuration's ``control_precision`` in the program's
    place."""
    from types import SimpleNamespace

    from reference.dro_lm import Numerics

    run = SimpleNamespace(traffic=spec.traffic, config=spec.config,
                          seed=seed)
    ref = follow(run, Numerics())
    low = follow(run, Numerics(spec.config["control_precision"]))
    return compare(low, ref, spec.traffic)
