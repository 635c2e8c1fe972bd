"""Mesh: the share of each chip's busy time in the traced window in which
a collective operation runs (the union of the operations that
``tracereduce.COLLECTIVE`` matches over the union of all its operations),
mean over the chips. An operation is matched by its own name, the text
before `` = ``: the rest of its HLO text names its operands, and an
operation that consumes an all-gather's result is not a collective."""
from chipbench import tracereduce


def own_name_matches(name: str) -> bool:
    end = name.find(" = ")
    return tracereduce.COLLECTIVE.search(
        name, 0, end if end >= 0 else len(name)) is not None


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    shares = []
    for spans in run.trace.ops.values():
        busy = tracereduce.busy_s(spans, lo, hi)
        if busy > 0:
            coll = [s for s in spans if own_name_matches(s.name)]
            shares.append(tracereduce.busy_s(coll, lo, hi) / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
