"""On-chip smoke test of the trainer's main path.

Default (one TPU chip), two phases:

1. ``train``: ``repro.launch.train`` exactly as its CLI runs it — paper-toy
   at its own widths (12 layers, d=768, vocab 32000), n=4 clients, K=2,
   per-client batch 2 at seq 512, ``--mesh host --engine scan --chunk 4``,
   ``ROUNDS`` rounds at x step ``ETA_CX``: one compile, then steady
   chunks.  Passes when every logged metric is finite and the held-out
   loss at the last logged round is below that at round 0.
2. ``kernel``: the paper quadratic through ``repro.sweep.run.run_point`` at
   n=8, K=8 on a ring with ``mixing_impl="fused_round"`` and
   ``gossip_backend="auto"`` — the whole round as one Pallas kernel.  Passes
   when the compiled chunk holds a ``tpu_custom_call``, its ‖∇Φ‖ trajectory
   falls, and it matches the same point on the XLA oracle within
   ``KERNEL_RTOL``.

``--four-chips`` runs only the sharded path and its comparison: the same
paper-toy job with ``--mesh decentralized`` over four chips (one client per
chip), then with ``--mesh host`` on one chip.  Passes when the logged losses
agree within ``MESH_RTOL``, every ``state.x`` leaf holds its 4 client rows
on 4 distinct devices, and each device's peak memory is at most
``MESH_PEAK_SHARE`` of the one-chip run's.

The last line of standard output is ``{"ok": true, "device": {...}}`` and
is printed only when every phase passed.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no result; it never falls back
to the CPU.  Run from the repository root:

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: Rounds of the train phase, in chunks of CHUNK rounds (one compile: every
#: chunk has the same length).  Each chunk logs its first round.  At full
#: width the held-out loss moves slowly — 32000 embedding rows each see few
#: tokens per step under plain SGD — so 8 rounds cannot show it fall: on a
#: v5e it read 5.26255 -> 5.26387 over 8 rounds at the default steps.
ROUNDS = 64
CHUNK = 4

#: The x step size.  At 0.01 the job stayed finite and the held-out loss
#: fell, 5.262521 -> 5.259042 over 64 rounds on a v5e with f32 gossip at
#: full precision.  The default 0.05 was NaN by round 36 on a v5e, but only
#: in runs whose gossip rounded the parameters to bf16 each round; it has
#: not yet been run on a chip with that rounding removed.
ETA_CX = 0.01

#: The train phase's job, as CLI arguments (``--seed`` is appended).
TRAIN_ARGV = [
    "--arch", "paper-toy", "--clients", "4", "--local-steps", "2",
    "--batch", "2", "--seq-len", "512", "--mesh", "host", "--engine", "scan",
    "--chunk", str(CHUNK), "--rounds", str(ROUNDS), "--log-every", str(CHUNK),
    "--eta-cx", str(ETA_CX),
]

#: The kernel phase's point (``seed`` and ``gossip_backend`` are added).
#: eps=0 never stops early, so both backends run the same 160 rounds.
KERNEL_POINT = dict(n=8, K=8, topology="ring", mixing_impl="fused_round",
                    eps=0.0, max_rounds=160, eval_every=10)

#: Relative tolerance between the Pallas kernel's and the XLA oracle's
#: ‖∇Φ‖ at each evaluation.  Both contract f32 at full precision, but in
#: different orders, and each round feeds the next: on a v5e the two
#: trajectories differed by at most 1.3e-6 relative over 160 rounds.  2e-2
#: leaves room for a compiler that splits the f32 contraction into fewer
#: bf16 passes (relative error ~2^-9 per operand); a wrong mixing weight,
#: sign or correction changes ‖∇Φ‖ by tens of percent or makes it diverge.
KERNEL_RTOL = 2e-2

#: Relative tolerance between the sharded four-chip run's and the one-chip
#: run's logged losses.  Both compute the model in bf16; sharding changes
#: the order of the cross-client reductions of the gossip and the metrics,
#: which bf16 activations amplify to ~1e-3 relative per round.
MESH_RTOL = 2e-2

#: Highest allowed ratio of a device's peak bytes on four chips to the
#: one-chip run's peak.  Each chip holds one client's quarter of the state
#: and activations; the replicated mean model x̄ that the metrics evaluate
#: (~0.4 GB) and the gossip's gathered buffers come on top.
MESH_PEAK_SHARE = 0.4


class PhaseFailed(Exception):
    """A check of one phase failed; the other phases still run."""


def fail(msg: str) -> None:
    raise PhaseFailed(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        fail(f"{dev} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def finite(value) -> bool:
    if isinstance(value, list):
        return all(finite(v) for v in value)
    return math.isfinite(value)


def client_rows(jax, state) -> dict:
    """Where each ``state.x`` leaf's client rows live:
    ``{leaf path: sorted [(first row, rows, device id)] per shard}``."""
    return {
        jax.tree_util.keystr(path): sorted(
            (s.index[0].start or 0, s.data.shape[0], s.device.id)
            for s in leaf.addressable_shards)
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.x)}


def run_train(jax, train_lib, seed: int, mesh: str) -> tuple:
    """One train() call as the CLI makes it; returns (history, placement):
    the history of logged rounds and ``client_rows`` of the final state."""
    argv = TRAIN_ARGV + ["--seed", str(seed)]
    argv[argv.index("--mesh") + 1] = mesh
    args = train_lib.build_parser().parse_args(argv)
    placement = {}

    def read_placement(state, records, prev_round):
        placement.update(client_rows(jax, state))

    hist = train_lib.train(args, hooks=[read_placement])["history"]
    if not hist:
        fail(f"train ({mesh}) logged no rounds")
    for rec in hist:
        bad = [k for k, v in rec.items() if not finite(v)]
        if bad:
            fail(f"train ({mesh}) round {rec['round']}: non-finite {bad}")
    return hist, placement


def train_summary(hist) -> dict:
    """Compile seconds, steady seconds per round and the loss trajectory.
    A record's wall_s is stamped after its chunk's state is ready, so the
    steady time is the wall between the end of the first chunk (compile
    plus one execution) and the end of the last."""
    first_end = max(r["wall_s"] for r in hist if r["round"] < CHUNK)
    steady_rounds = ROUNDS - CHUNK
    return {
        "compile_s": hist[-1]["compile_s"],
        "steady_s_per_round": (hist[-1]["wall_s"] - first_end) / steady_rounds,
        "eval_loss": [(r["round"], r["eval_loss"]) for r in hist],
        "mean_loss": [(r["round"], r["mean_loss"]) for r in hist],
        "y_bar_norm": [(r["round"], r["y_bar_norm"]) for r in hist],
    }


def phase_train(jax, seed: int) -> None:
    from repro.configs import registry
    from repro.launch import train as train_lib
    from repro.models import model as model_lib

    cfg = registry.get_model_config("paper-toy")
    n_params = model_lib.param_count(jax.eval_shape(
        lambda key: model_lib.init_params(cfg, key), jax.random.PRNGKey(0)))
    say(f"train: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; argv: {' '.join(TRAIN_ARGV)} --seed {seed}")
    t0 = time.perf_counter()
    hist, _ = run_train(jax, train_lib, seed, "host")
    s = train_summary(hist)
    peak = peak_bytes(jax.devices()[0])
    say(f"train: params per client {n_params}, compile_s={s['compile_s']}, "
        f"steady_s_per_round={s['steady_s_per_round']:.4f}, "
        f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB), "
        f"wall {time.perf_counter() - t0:.1f}s")
    say(f"train: eval_loss {s['eval_loss']}")
    say(f"train: mean_loss {s['mean_loss']}")
    say(f"train: y_bar_norm {s['y_bar_norm']}")
    first, last = hist[0]["eval_loss"], hist[-1]["eval_loss"]
    if not last < first:
        fail(f"held-out loss did not fall: {first} -> {last}")
    say(f"train: PASS (eval_loss {first:.4f} -> {last:.4f})")


def phase_kernel(seed: int) -> None:
    from repro.sweep import run as sweep_run

    pallas = dict(KERNEL_POINT, seed=seed, gossip_backend="auto")
    oracle = dict(pallas, gossip_backend="xla")
    hlo = sweep_run.point_program_text(pallas, KERNEL_POINT["eval_every"])
    n_calls = hlo.count("tpu_custom_call")
    say(f"kernel: fused_round chunk HLO has {n_calls} tpu_custom_call")
    if n_calls == 0:
        fail("fused_round with gossip_backend=auto compiled no Pallas kernel")
    _, _, t_k, h_k = sweep_run.run_point(pallas, cache=None)
    _, _, t_x, h_x = sweep_run.run_point(oracle, cache=None)
    say(f"kernel: pallas timing {t_k}")
    say(f"kernel: xla    timing {t_x}")
    worst = 0.0
    for (r, gk), (_, gx) in zip(h_k, h_x):
        if not (math.isfinite(gk) and math.isfinite(gx)):
            fail(f"kernel: non-finite ‖∇Φ‖ at round {r}: {gk} vs {gx}")
        worst = max(worst, abs(gk - gx) / abs(gx))
    say(f"kernel: ‖∇Φ‖ pallas {[round(g, 6) for _, g in h_k]}")
    say(f"kernel: ‖∇Φ‖ xla    {[round(g, 6) for _, g in h_x]}")
    say(f"kernel: max relative difference {worst:.3e} (rtol {KERNEL_RTOL})")
    if len(h_k) != len(h_x) or worst > KERNEL_RTOL:
        fail("fused_round kernel disagrees with the XLA oracle")
    if not h_k[-1][1] < h_k[0][1]:
        fail(f"kernel: ‖∇Φ‖ did not fall: {h_k[0][1]} -> {h_k[-1][1]}")
    say("kernel: PASS")


def phase_four_chips(jax, seed: int) -> None:
    from repro.launch import train as train_lib

    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    say(f"mesh: paper-toy, 4 clients over {len(devs)} chips")
    hist_d, placement = run_train(jax, train_lib, seed, "decentralized")
    if not placement:
        fail("no chunk boundary reported the final state")
    for path, rows in placement.items():
        if ([(r, k) for r, k, _ in rows] != [(0, 1), (1, 1), (2, 1), (3, 1)]
                or len({d for _, _, d in rows}) != 4):
            fail(f"state.x{path} is not one client row per device: {rows}")
    say(f"mesh: every state.x leaf holds one client row per device; "
        f"(row, rows, device id) {rows}")
    peaks_d = [peak_bytes(d) for d in devs]
    s_d = train_summary(hist_d)
    say(f"mesh: decentralized compile_s={s_d['compile_s']}, "
        f"steady_s_per_round={s_d['steady_s_per_round']:.4f}, "
        f"peak_bytes_in_use per device {peaks_d}")
    hist_h, _ = run_train(jax, train_lib, seed, "host")
    peak_h = peak_bytes(devs[0])
    s_h = train_summary(hist_h)
    say(f"mesh: host compile_s={s_h['compile_s']}, "
        f"steady_s_per_round={s_h['steady_s_per_round']:.4f}, "
        f"peak_bytes_in_use {peak_h}")
    worst = 0.0
    for rd, rh in zip(hist_d, hist_h):
        for key in ("eval_loss", "mean_loss", "f_bar"):
            worst = max(worst, abs(rd[key] - rh[key]) / abs(rh[key]))
    say(f"mesh: eval_loss decentralized {s_d['eval_loss']}")
    say(f"mesh: eval_loss host          {s_h['eval_loss']}")
    say(f"mesh: max relative loss difference {worst:.3e} (rtol {MESH_RTOL})")
    if len(hist_d) != len(hist_h) or worst > MESH_RTOL:
        fail("the sharded run's losses disagree with the one-chip run's")
    share = max(peaks_d) / peak_h
    say(f"mesh: largest per-device peak / one-chip peak = {share:.3f} "
        f"(limit {MESH_PEAK_SHARE})")
    if share > MESH_PEAK_SHARE:
        fail("a device holds more than its share of the job")
    say("mesh: PASS")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-client-per-chip job on 4 chips "
                         "and its one-chip comparison")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        sys.exit(f"[chip_smoke] FAIL: no repro package under {REPO}/src: "
                 "run from a checkout")
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    info = device_info(jax)
    say(f"devices: {info}")
    if info["platform"] != "tpu":
        sys.exit(f"[chip_smoke] FAIL: no TPU: JAX reports "
                 f"{info['platform']!r} devices")

    from repro.sweep import cache as cache_lib

    say(f"compile cache: {cache_lib.enable_xla_cache()}")
    if args.four_chips:
        phases = [("mesh", lambda: phase_four_chips(jax, args.seed))]
    else:
        phases = [("train", lambda: phase_train(jax, args.seed)),
                  ("kernel", lambda: phase_kernel(args.seed))]
    failed = []
    for name, phase in phases:
        try:
            phase()
        except PhaseFailed as e:
            print(f"[chip_smoke] FAIL ({name}): {e}", file=sys.stderr,
                  flush=True)
            failed.append(name)
    if failed:
        sys.exit(f"[chip_smoke] failed phases: {failed}")
    print(json.dumps({"ok": True, "device": device_info(jax)}), flush=True)


if __name__ == "__main__":
    main()
