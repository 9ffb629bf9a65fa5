"""Device time per round by layer for one benchmark cell, with the forward
and backward of a sparse-expert model split further by its ``moe.route``,
``moe.experts`` and ``moe.combine`` scopes.

Runs one traced run of a cell as ``bench/run.py --trace 1`` does, reads
the trace through ``bench/scopes.py`` before the harness deletes it, and
prints one JSON line: milliseconds per round of each layer (``forward``,
``backward``, …, ``forward:moe.experts``, …), the window's rounds, and the
ten ops with most device time under each MoE scope.  On the chip:

    python3 scripts/layer_split.py --workload granite-ep4-n4-k2 --seed 7 --seconds 30
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MOE_SCOPE = re.compile(r"(?:^|/)(moe\.(?:route|experts|combine))(?=/|$)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]
    import jax

    import run as bench_run
    import scopes
    import trace_reader as tr

    layer_of = scopes.layer_of

    def split_layer_of(instruction, op_name):
        layer = layer_of(instruction, op_name)
        m = MOE_SCOPE.findall(op_name or "")
        if m and layer in ("forward", "backward"):
            return f"{layer}:{m[-1]}|{instruction}"
        return f"{layer}|{instruction}"

    captured = {}
    read_trace = bench_run.Run.read_trace

    def read_and_split(run):
        path = tr.latest_xplane(run._trace_dir)
        with open(path, "rb") as f:
            xspace = f.read()
        scopes.layer_of = split_layer_of
        try:
            captured["ops"] = scopes.layered_ops(tr.load(path), xspace)
        finally:
            scopes.layer_of = layer_of
        read_trace(run)
        captured["window"] = (run.trace.lo, run.trace.hi)
        captured["rounds"] = run.counts["rounds"]

    bench_run.Run.read_trace = read_and_split
    spec = bench_run.cell_spec(args.workload)
    bench_run.look_for_chips(jax, spec)
    line, _ = bench_run.execute(spec, args.seed, args.seconds, True, jax)
    lo, hi = captured["window"]
    ops = captured["ops"]
    chips = [c for c in sorted(ops) if tr.clip(ops[c], lo, hi)]
    per_layer, per_op = {}, {}
    for c in chips:
        for label, ns in scopes.exclusive_ns(ops[c], lo, hi).items():
            layer, op = label.split("|", 1)
            per_layer[layer] = per_layer.get(layer, 0.0) + ns
            if ":" in layer:
                top = per_op.setdefault(layer, {})
                top[op] = top.get(op, 0.0) + ns
    scale = 1e-6 / len(chips) / captured["rounds"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "rounds": captured["rounds"],
        "round_ms": {k: v * scale for k, v in sorted(per_layer.items())},
        "moe_top_ops_ms": {
            k: sorted(((op, ns * scale) for op, ns in v.items()),
                      key=lambda t: -t[1])[:10]
            for k, v in sorted(per_op.items())},
        "line": line}), flush=True)


if __name__ == "__main__":
    main()
