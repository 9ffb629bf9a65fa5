"""Share of the traced window in which a collective runs on a chip with no
compute beside it (``trace_reader.Trace.exposed_collective_s``: the
exchange that compute does not hide), averaged over the cell's chips; None
without a device op to read."""
from __future__ import annotations


def read(run):
    if run.trace is None or run.trace.busy_s() <= 0:
        return None
    return 100.0 * run.trace.exposed_collective_s() / run.trace.window_s
