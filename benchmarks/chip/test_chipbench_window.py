"""Window arithmetic on synthetic timestamps."""

import math

import pytest

from chipbench import window
from chipbench.window import Record


def steady(n_req=20, tokens=10, step=0.1, stalls=()):
    """Requests due every 0.5 s, first token 0.2 s later, then one token
    per ``step``; each ``(at, seconds)`` stall delays every token after
    ``at`` by ``seconds``."""
    recs = []
    for i in range(n_req):
        due = 0.5 * i
        times = [due + 0.2 + k * step for k in range(tokens)]
        for at, sec in stalls:
            times = [t + sec if t >= at else t for t in times]
        recs.append(Record(index=i, due=due, prompt_len=10, max_new=tokens,
                           greedy=True, token_times=times,
                           finish_reason="done"))
    return recs


def test_tails_are_over_all_requests_and_all_gaps():
    recs = steady()
    ttft = window.ttft_values(recs, 0.0, 10.0)
    assert len(ttft) == 20 and all(abs(t - 0.2) < 1e-9 for t in ttft)
    recs[3].token_times = [t + 1.0 for t in recs[3].token_times]
    assert window.tail_ms(window.ttft_values(recs, 0.0, 10.0), 1.0) \
        == pytest.approx(1e3 * 1.2)
    gaps = window.itl_values(recs, 0.0, 10.0)
    assert len(gaps) == sum(1 for r in recs
                            for t in r.token_times[1:] if t < 10.0)
    assert window.quantile([1, 2, 3, 4, 5], 0.9) == pytest.approx(4.6)


def test_failed_or_unfinished_requests_rank_slowest():
    recs = steady()
    recs[0].finish_reason = "failed"
    recs[1].token_times = []
    vals = window.ttft_values(recs, 0.0, 10.0)
    assert sorted(vals)[-2:] == [math.inf, math.inf]
    # the top two of 20 are the tail's 95th percentile and above: read at
    # the censoring time given
    assert window.tail_ms(vals, 0.99, censor_s=7.5) == 7500.0
    assert window.tail_ms(vals, 0.5, censor_s=7.5) == pytest.approx(200.0)


def test_a_stall_in_the_window_moves_itl_tail_and_rate():
    base = steady()
    hit = steady(stalls=[(t + 0.05, 0.8) for t in range(1, 10)])
    assert window.tail_ms(window.itl_values(hit, 0, 10), 0.95) \
        > window.tail_ms(window.itl_values(base, 0, 10), 0.95)
    assert window.output_tok_s(hit, 0, 10) < window.output_tok_s(base, 0, 10)
