"""Cells cut down to what a CPU test run can hold: the toy job at the
model's smoke-test width (2 layers, d=256, vocab 512, seq 32).  Limits
stay the cells' own."""
import os

import run as bench_run

SMALL_TOY = {"num_layers": 2, "d_model": 256, "num_heads": 4,
             "num_kv_heads": 4, "head_dim": 64, "d_ff": 512,
             "vocab_size": 512}


def spec(workload: str):
    s = bench_run.cell_spec(workload)
    if s.cell["entry"] == "train":
        s.config = dict(s.config, **SMALL_TOY)
        argv = list(s.traffic["argv"])
        argv[argv.index("--seq-len") + 1] = "32"
        argv[argv.index("--mesh") + 1] = "host"
        s.traffic = dict(s.traffic, argv=argv + ["--reduced"])
    s.chips = 1
    return s


def entry(s):
    return bench_run.load_module(
        os.path.join(bench_run.BENCH, "entries", s.cell["entry"] + ".py"),
        "bench_entry_" + s.cell["entry"])
