"""Step programs: the least time for what one decode step must move and
compute (every matrix once at the compute dtype, the batch's embedding
rows, the new KV rows, the live KV rows and the q and output rows; the
larger of that over the chips' HBM bandwidth and its FLOPs over their
peak, split ideally over the cell's chips), averaged over the window's
decode dispatches, over the mean device time of a decode execution."""
from chipbench import cost, tracereduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    per_chip = tracereduce.decode_executions(run.trace, *run.trace_window)
    means = [sum(s.dur for s in v) / len(v) for v in per_chip.values() if v]
    steps = [kv for t, _, kv in run.steps if run.t0 <= t < run.t1]
    if not means or not steps:
        return None
    s = run.shape
    least = [cost.least_seconds(s.decode_flops(kv), s.decode_bytes(kv),
                                run.peak, run.chips) for kv in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(means) / len(means))
