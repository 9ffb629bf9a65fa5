"""Model FLOP utilization of the training window: the forward and
backward FLOPs per token of the configuration (``flops.py``; no
recomputation, no eval forwards) times the tokens trained in the traced
window, over the window's seconds, the chips and the chip's bf16 peak."""
from __future__ import annotations


def read(run):
    if run.trace is None or not run.counts.get("tokens"):
        return None
    seq = int(run.traffic["argv"][run.traffic["argv"].index("--seq-len") + 1])
    per_token = run.flops.dense_lm_train_flops_per_token(run.config, seq)
    achieved = per_token * run.counts["tokens"] / run.trace.window_s
    return 100.0 * achieved / (run.spec.chips * run.peaks["bf16_flops"])
