"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (1 − union of device op intervals / window);
None without a device op to read."""
from __future__ import annotations


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    if share is None or run.trace.busy_s() <= 0:
        return None
    return 100.0 * share
