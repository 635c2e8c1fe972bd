"""95th percentile of the gap between consecutive output tokens of one
request, as the host reads them back, over every gap ending in the
window."""
from chipbench import window


def read(run):
    vals = window.itl_values(run.records, run.t0, run.t1)
    return window.tail_ms(vals, 0.95) if vals else None
