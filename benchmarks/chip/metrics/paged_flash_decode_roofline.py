"""Kernels: the least time for the ``paged_flash_decode`` calls (each
slot's live K and V rows read, its q and output rows, and the attention
FLOPs, on one chip's peaks) over the kernel's device time by its calls in
the traced window, summed over the chips. One call is one layer of one
decode step on one chip, so a call's least time is the mean least time
of a window step's attention over the layers, split ideally over the
chips."""
from chipbench import cost, tracereduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    lo, hi = run.trace_window
    serving = run.cell.cfg["serving"]
    table = serving["slots"] * serving["max_seq"] // serving["page_size"]
    calls = [s for spans in run.trace.ops.values()
             for s in tracereduce.clip(spans, lo, hi)
             if tracereduce.paged_kernel(s, table)]
    steps = [kv for t, _, kv in run.steps if run.t0 <= t < run.t1]
    if not calls or not steps:
        return None
    s = run.shape
    least = [cost.least_seconds(s.attn_flops(sum(kv)), s.attn_bytes(kv),
                                run.peak) for kv in steps]
    per_call = sum(least) / len(least) / (s.layers * run.chips)
    return 100.0 * per_call * len(calls) / sum(c.dur for c in calls)
