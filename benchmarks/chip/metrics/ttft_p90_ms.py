"""90th percentile of time to first token over every request due in the
window, each timed from when it was due. A request that failed, or had no
token when the post-window drain ended, ranks above every other (read at
the drain's end where the percentile reaches it)."""
from chipbench import window


def read(run):
    vals = window.ttft_values(run.records, run.t0, run.t1)
    if not vals:
        return None
    worst = max(run.drain_end - r.due for r in run.records
                if run.t0 <= r.due < run.t1)
    return window.tail_ms(vals, 0.90, worst)
