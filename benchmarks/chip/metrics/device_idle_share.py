"""Device: the share of the traced window in which no operation runs,
mean over the cell's chips."""
from chipbench import tracereduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    busy = [tracereduce.busy_s(s, lo, hi) for s in run.trace.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
