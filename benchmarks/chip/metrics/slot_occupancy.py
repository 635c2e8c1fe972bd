"""Scheduler: occupied decode slots over all slots, read from
``engine.slots`` after each ``step()`` that dispatched in the window,
averaged over those dispatches."""


def read(run):
    occ = [n for t, n, _ in run.steps if run.t0 <= t < run.t1]
    return 100.0 * sum(occ) / (len(occ) * run.slots) if occ else None
