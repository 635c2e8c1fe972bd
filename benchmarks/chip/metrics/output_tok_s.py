"""Output tokens read back by the host in the window, per second."""
from chipbench import window


def read(run):
    return window.output_tok_s(run.records, run.t0, run.t1)
