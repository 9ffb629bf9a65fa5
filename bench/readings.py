"""Readings that set a cell's limits: the numbers the comparison computes,
per seed, for the program, for the control, or for the program with a
planted fault.  Run on the chip at the cell's own size:

    python3 bench/readings.py --workload <cell> --mode program --seeds 1,2,3
    python3 bench/readings.py --workload <cell> --mode control --seeds 1,2,3
    python3 bench/readings.py --workload <cell> --mode fault:half_batch --seeds 1,2,3

``program`` and ``fault:*`` drive the run's set-up and a short window
(``--seconds``, default 0: one call past the set-up) and compare as a
benchmark run does.  ``control`` puts the plain reference in the
program's place, computed in the precision just below the one the
configuration states (its ``control_precision``), and compares
it with the reference.  One JSON line per seed goes to standard output.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    import faults
    import run as bench_run

    spec = bench_run.cell_spec(args.workload)
    bench_run.look_for_chips(jax, spec)
    entry = bench_run.load_module(
        os.path.join(BENCH, "entries", spec.cell["entry"] + ".py"),
        "bench_entry_" + spec.cell["entry"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.mode == "control":
            numbers = entry.control_readings(spec, seed)
        elif args.mode == "program":
            _, numbers = bench_run.execute(spec, seed, args.seconds, False,
                                           jax)
        elif args.mode.startswith("fault:"):
            with faults.FAULTS[args.mode.split(":", 1)[1]]():
                _, numbers = bench_run.execute(spec, seed, args.seconds,
                                               False, jax)
        else:
            raise SystemExit(f"unknown mode {args.mode!r}")
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "numbers": numbers}), flush=True)


if __name__ == "__main__":
    main()
