"""The trace reduction, on a trace recorded on a TPU v5e: nine decode
steps of the ``qwen2-0.5b.chat`` cell (32 slots, max_seq 4096), about
1.6 s, taken by the harness with its host spans."""

from pathlib import Path

import pytest

from chipbench import tracereduce as tr
from chipbench.tracereduce import Span

TRACE = Path(__file__).resolve().parent / "testdata" \
    / "qwen2-0.5b.chat.small.xplane.pb.gz"
TABLE = 32 * 4096 // 16          # the page table's entries


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def test_planes_lines_and_host_spans(trace):
    assert sorted(trace.ops) == [0] and len(trace.ops[0]) > 10_000
    lo, hi = trace.window()
    assert 1.5 < hi - lo < 1.7
    names = {s.name for s in trace.host}
    assert {"bench.window", "bench.step", "bench.observe"} <= names


def test_busy_idle_and_gaps(trace):
    lo, hi = trace.window()
    busy = tr.busy_s(trace.ops[0], lo, hi)
    gaps = tr.idle_gaps(trace.ops[0], lo, hi)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    assert 0.95 < busy / (hi - lo) <= 1.0
    b = tr.breakdown(trace, lo, hi)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])


def test_programs_kernels_and_collectives(trace):
    lo, hi = trace.window()
    decode = tr.decode_executions(trace, lo, hi)[0]
    assert len(decode) == 9
    assert 0.15 < sum(s.dur for s in decode) / 9 < 0.25
    calls = [s for s in tr.clip(trace.ops[0], lo, hi)
             if tr.paged_kernel(s, TABLE)]
    assert len(calls) == 8 * 24           # 24 layers of the traced steps
    assert not any(tr.COLLECTIVE.search(s.name) for s in trace.ops[0])


def test_span_arithmetic():
    spans = [Span("a", 0.0, 1.0), Span("b", 0.5, 2.0), Span("c", 3.0, 4.0)]
    assert tr.union(spans) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.busy_s(spans, 0.5, 3.5) == pytest.approx(2.0)
    assert tr.idle_gaps(spans, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    host = [Span("bench.step", 1.5, 2.6), Span("bench.observe", 2.6, 2.7)]
    assert tr.name_gap((2.0, 3.0), host) == "bench.step"
    assert tr.name_gap((4.0, 5.0), host) == "(no host span)"
