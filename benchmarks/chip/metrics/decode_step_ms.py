"""Step programs: device time of the decode program's executions in the
traced window over their count, mean over the chips."""
from chipbench import tracereduce


def read(run):
    if run.trace is None:
        return None
    per_chip = tracereduce.decode_executions(run.trace, *run.trace_window)
    means = [sum(s.dur for s in v) / len(v) for v in per_chip.values() if v]
    return 1e3 * sum(means) / len(means) if means else None
