"""The serving engine's host spans and ``Request.t_admit``: a traced run
of the tiny cell on the CPU, the spans' arguments, the admission time in
the queue and under preemption, and the spans of a trace recorded on a
TPU v5e. The harness keeps host spans whose names start ``bench.``; these
tests widen that to ``engine.`` to see the program's spans."""

import glob
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import chipbench_tiny as tiny
from chipbench import harness, tracereduce
from chipbench.tracereduce import Span
from repro import configs
from repro.models import registry
from repro.serving.cache_manager import CacheConfig
from repro.serving.engine import Engine, Request

HERE = Path(__file__).resolve().parent
RECORDING = HERE / "testdata" / "qwen2-0.5b.chat.spans.xplane.pb.gz"
SEED = 2**31 + 91
WITH_ENGINE = ("bench.", "engine.")
ENGINE_SPANS = {"engine.step", "engine.admit", "engine.first_token",
                "engine.ensure_pages", "engine.dispatch", "engine.readback"}


def nested(spans):
    """Every engine span other than ``engine.step`` lies inside one."""
    steps = [s for s in spans if s.name == "engine.step"]
    return all(any(st.start <= s.start and s.end <= st.end for st in steps)
               for s in spans if s.name != "engine.step")


def test_traced_tiny_run_records_the_engine_spans(monkeypatch):
    seen = {}
    load, window = tracereduce.load, harness.Load.window

    def keep(path):
        seen["trace"] = load(path)
        return seen["trace"]

    def counted(self, seconds):
        before = self.engine.stats()["readbacks"]
        out = window(self, seconds)
        seen["readbacks"] = self.engine.stats()["readbacks"] - before
        return out

    monkeypatch.setattr(tracereduce, "HOST_PREFIX", WITH_ENGINE)
    monkeypatch.setattr(tracereduce, "load", keep)
    monkeypatch.setattr(harness.Load, "window", counted)
    result = harness.run(tiny.cell(), SEED, 2.0, True, require_chip=False)
    assert result["correct"]
    assert set(result["metrics"]) == {"slot_occupancy", "prefix_hit_share"}

    trace = seen["trace"]
    engine = [s for s in trace.host if s.name.startswith("engine.")]
    assert {s.name for s in engine} == ENGINE_SPANS
    assert nested(engine)
    lo, hi = trace.window()
    readbacks = [s for s in engine
                 if s.name == "engine.readback" and lo <= s.start <= hi]
    assert len(readbacks) == seen["readbacks"] > 0


def small_engine(**kw):
    cfg = configs.smoke("qwen2-0.5b")
    params = registry.init(cfg, jax.random.PRNGKey(0))[0]
    return cfg, Engine(params, cfg, **kw)


def prompts(cfg, lengths):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
            for n in lengths]


def test_engine_spans_carry_the_request_id_and_the_step_number(tmp_path):
    """One slot, two requests: each admission's span names its request,
    each step's its number, and a step with nothing to do still records
    its span."""
    cfg, eng = small_engine(slots=1, max_seq=64)
    for rid, p in zip((5, 9), prompts(cfg, [7, 9])):
        eng.submit(Request(rid=rid, max_new_tokens=3, prompt=p))
    with jax.profiler.trace(str(tmp_path)):
        done = eng.run()
        assert not eng.step()
    assert sorted(r.rid for r in done) == [5, 9]
    data = ProfileData.from_file(
        glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0])
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for p in data.planes if p.name == tracereduce.HOST_PLANE
              for line in p.lines for e in line.events
              if e.name.startswith("engine.")]
    assert nested([Span(n, s, e) for n, s, e, _ in events])
    assert [st["rid"] for n, _, _, st in events if n == "engine.admit"] \
        == [5, 9]
    # one span per call, numbered by the steps dispatched before it: a
    # call that dispatches nothing (the last) repeats the number
    steps = [st["step_num"] for n, _, _, st in events if n == "engine.step"]
    assert steps[0] == 0 and steps[-1] == steps[-2] == eng.stats()["steps"]
    assert all(b - a in (0, 1) for a, b in zip(steps, steps[1:]))


def test_t_admit_waits_for_a_free_slot():
    cfg, eng = small_engine(slots=1, max_seq=64)
    first, second = (Request(rid=rid, max_new_tokens=4, prompt=p)
                     for rid, p in enumerate(prompts(cfg, [11, 13])))
    eng.submit(first)
    eng.submit(second)
    while eng.step() and not first.done:
        assert second.t_admit == 0.0     # queued while the slot is held
    eng.run()
    assert first.done and second.done
    assert 0 < second.t_submit < first.t_first < second.t_admit \
        <= second.t_first


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_t_admit_is_the_first_pop_between_submit_and_first_token(
        monkeypatch, mode):
    """A pool of four 16-row pages for three requests of up to 53 rows:
    requests are preempted and re-admitted, and each keeps the admission
    time of its first pop."""
    at_preemption = {}
    preempt = Engine._preempt

    def noting(self, victim):
        req = self.slots[victim].req
        assert req.t_admit > 0
        at_preemption.setdefault(req.rid, req.t_admit)
        preempt(self, victim)

    monkeypatch.setattr(Engine, "_preempt", noting)
    cfg, eng = small_engine(
        slots=3, max_seq=64, preemption=mode,
        cache_manager=CacheConfig(page_size=16, num_pages=4))
    for rid, p in enumerate(prompts(cfg, [20, 17, 23])):
        eng.submit(Request(rid=rid, max_new_tokens=30, prompt=p))
    done = eng.run()
    assert len(done) == 3 and all(r.finish_reason == "done" for r in done)
    assert at_preemption and eng.stats()["preemptions"] >= 1
    for r in done:
        assert 0 < r.t_submit <= r.t_admit <= r.t_first
        if r.rid in at_preemption:
            assert r.t_admit == at_preemption[r.rid]


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    host = [Span("bench.step", 1.19, 1.5), Span("bench.step", 1.49, 1.9),
            Span("engine.step", 1.2, 1.5),
            Span("engine.step", 1.5, 1.9),
            Span("engine.admit", 1.5, 1.75),
            Span("engine.first_token", 1.51, 1.71),
            Span("engine.dispatch", 1.75, 1.8),
            Span("engine.readback", 1.8, 1.89)]
    assert tracereduce.name_gap((1.71, 1.74), host) == "engine.admit"
    assert tracereduce.name_gap((1.76, 1.79), host) == "engine.dispatch"
    assert tracereduce.name_gap((1.46, 1.49), host) == "engine.step"


def test_the_engine_spans_on_a_trace_recorded_on_the_chip(monkeypatch):
    """1.67 s of ``qwen2-0.5b.chat`` on a TPU v5e in its steady state
    (9 decode steps), taken by the harness's set-up and ``Load`` after one
    untraced second of the schedule, with the schedule's next request
    sent 0.6 s into the traced window while a decode step runs."""
    monkeypatch.setattr(tracereduce, "HOST_PREFIX", WITH_ENGINE)
    trace = tracereduce.load(RECORDING)
    lo, hi = trace.window()
    engine = [s for s in trace.host if s.name.startswith("engine.")]
    assert {s.name for s in engine} == ENGINE_SPANS
    assert nested(engine)
    assert len(tracereduce.decode_executions(trace, lo, hi)[0]) == 9
    # the one admission's first-token read waits for the decode step in
    # flight (212 ms) and then for its prefill
    reads = [s.dur for s in engine if s.name == "engine.first_token"
             and lo <= s.start <= hi]
    assert len(reads) == 1 and 0.2 < reads[0] < 0.3
    # the device idles only after the first-token read, until the next
    # decode step starts: about 2.6 ms, inside the step
    gaps = tracereduce.breakdown(trace, lo, hi)["idle_gaps"]
    assert gaps[0][0] == "engine.step" and 0.002 < gaps[0][1] < 0.0035
    assert not any(name == "bench.step" for name, _ in gaps)
