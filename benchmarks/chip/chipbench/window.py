"""Arithmetic of the measured window, from host timestamps alone.

A ``Record`` is what the load generator saw of one request: when it was
due, when it was submitted, and the host time at which each of its
output tokens was read back. Every end-to-end metric is taken over all
the work and all the time of the window: a rate is the tokens read back
in the window over its length; a tail is the tail of every request due,
or of every gap that ends, in the window.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass
class Record:
    index: int
    due: float                      # host clock, seconds
    prompt_len: int
    max_new: int
    greedy: bool
    prompt: object = None           # token ids (numpy)
    temperature: float = 0.0
    sample_seed: int = 0            # the request's sampling seed
    submitted: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)
    admitted: Optional[float] = None    # first token seen = prefill done
    finish_reason: Optional[str] = None
    prefix_hit_tokens: int = 0
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def failed(self) -> bool:
        return self.finish_reason not in (None, "done")


def quantile(values, q: float, censor: float = math.inf) -> float:
    """The ``q`` quantile, interpolated between order statistics as
    numpy's default does. ``inf`` entries rank above every finite one;
    where the quantile reaches one, ``censor`` is returned (the longest
    time the run could have observed)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo, hi = xs[math.floor(pos)], xs[math.ceil(pos)]
    if math.isinf(hi):
        return censor
    return lo + (hi - lo) * (pos - math.floor(pos))


def tokens_in(records, t0: float, t1: float) -> int:
    return sum(t0 <= t < t1 for r in records for t in r.token_times)


def output_tok_s(records, t0: float, t1: float) -> float:
    """Output tokens read back by the host in ``[t0, t1)`` per second."""
    return tokens_in(records, t0, t1) / (t1 - t0)


def ttft_values(records, t0: float, t1: float) -> list:
    """Time to first token, from when each request was due, of every
    request due in ``[t0, t1)``; a failed request, or one with no token
    by the end of the run, is ``inf`` (slower than every other)."""
    out = []
    for r in records:
        if not t0 <= r.due < t1:
            continue
        if r.failed or not r.token_times:
            out.append(math.inf)
        else:
            out.append(r.token_times[0] - r.due)
    return out


def itl_values(records, t0: float, t1: float) -> list:
    """Gaps between consecutive tokens of one request, as read back by
    the host, for every gap that ends in ``[t0, t1)``."""
    return [b - a for r in records
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= b < t1]


def tail_ms(values, q: float, censor_s: float = math.inf) -> float:
    return 1e3 * quantile(values, q, censor_s)
