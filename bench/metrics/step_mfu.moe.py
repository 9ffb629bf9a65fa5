"""Model FLOP utilization of the training window on a sparse-expert share:
the FLOPs outside the experts per token (``moe_flops.py``) times the tokens
trained in the traced window, plus the expert FLOPs of the rows the window
routed to the held experts (the program's ``moe_rows_held`` per logged
round, times the window's rounds), over the window's seconds, the chips and
the chip's bf16 peak.  None without the counter."""
from __future__ import annotations

import moe_flops


def read(run):
    rows = run.counts.get("moe_rows_held_per_round")
    if run.trace is None or not run.counts.get("tokens") or rows is None:
        return None
    argv = run.traffic["argv"]
    seq = int(argv[argv.index("--seq-len") + 1])
    work = moe_flops.train_flops(run.config, seq, run.counts["tokens"],
                                 rows * run.counts["rounds"])
    achieved = work / run.trace.window_s
    return 100.0 * achieved / (run.spec.chips * run.peaks["bf16_flops"])
