"""The grouped expert products' share of their roofline in the traced
window: the time the chip would need for their FLOPs and bytes
(``moe_flops.grouped_products`` of the rows the window routed to the held
experts, forward and backward) over their device time.  Their device time
is that of the TPU's ragged-dot kernels, named ``ragged-dot-<…>.N`` in the
trace, without the kernels that prepare the groups' metadata
(``ragged-dot-metadata.N``).  None without the counter or the kernels."""
from __future__ import annotations

import moe_flops

#: The grouped products' kernels, as the trace names them.
PATTERN = r"^ragged-dot-(?!metadata)"


def read(run):
    rows = run.counts.get("moe_rows_held_per_round")
    if run.trace is None or rows is None or not run.counts.get("rounds"):
        return None
    device_s, count = run.trace.op_time_s(PATTERN)
    if not count or device_s <= 0:
        return None
    argv = run.traffic["argv"]
    arg = lambda flag: int(argv[argv.index(flag) + 1])
    rounds = run.counts["rounds"]
    calls = (arg("--clients") * arg("--local-steps")
             * run.config["num_layers"] * rounds)
    work = moe_flops.grouped_products(run.config, rows * rounds, calls)
    return 100.0 * moe_flops.roofline_s(work, run.peaks) / device_s
