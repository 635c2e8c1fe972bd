"""Request-lifecycle robustness + chaos-injection tests: abort/deadline
rollback through the refcounted pool (radix-shared pages included),
admission validation and the infeasibility watchdog, per-request failure
isolation (corrupt readbacks, prefill faults), crash-consistent recovery
from injected device-step faults (survivor streams bit-identical to an
undisturbed run), and the stream()/generate() no-silent-drop guarantee."""

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.reliability import Fault, FaultSchedule
from repro.serving.cache_manager import CacheConfig
from repro.serving.chaos import ChaosInjector, InjectedDeviceFault
from repro.serving.engine import Engine, ProgramError, Request
from repro.serving.api import LLMEngine
from repro.training.fault_tolerance import FailureInjector

_STATE = {}


def _setup():
    if not _STATE:
        cfg = configs.smoke("qwen2-0.5b")
        _STATE["cfg"] = cfg
        _STATE["params"] = registry.init(cfg, jax.random.PRNGKey(0))[0]
    return _STATE["cfg"], _STATE["params"]


def _prompts(cfg, n=4, length=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (length,), dtype=np.int32)
            for _ in range(n)]


def _run(cfg, params, prompts, *, max_new=8, slots=2, max_seq=64, **kw):
    eng = Engine(params, cfg, slots=slots, max_seq=max_seq, **kw)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p.copy(),
                           max_new_tokens=max_new))
    eng.run()
    return eng, {r.rid: list(r.out_tokens) for r in eng.finished}


# -- shared fault-schedule core ---------------------------------------------

def test_fault_schedule_fires_once_and_filters_kinds():
    sched = FaultSchedule([Fault("abort", step=3, rid=1),
                           Fault("device_fault", step=3, slot=0)])
    assert sched.due(2) == []
    only_abort = sched.due(3, kinds=("abort",))
    assert [f.kind for f in only_abort] == ["abort"]
    assert not sched.exhausted
    rest = sched.due(3)
    assert [f.kind for f in rest] == ["device_fault"]
    assert sched.due(3) == []          # fire-once
    assert sched.exhausted and sched.fired == 2


def test_failure_injector_back_compat():
    inj = FailureInjector(fail_at_step=5)
    inj.maybe_fail(4)
    assert not inj.fired
    with pytest.raises(RuntimeError, match="injected failure at step 5"):
        inj.maybe_fail(5)
    assert inj.fired
    inj.maybe_fail(5)                  # raises once, then inert
    FailureInjector(None).maybe_fail(0)


def test_chaos_injector_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown chaos fault kind"):
        ChaosInjector([Fault("meteor_strike", step=0)])


# -- abort / deadline --------------------------------------------------------

def test_abort_queued_and_resident_is_prefix_exact():
    """Abort one queued and one resident request mid-run: their streams
    are prefixes of the undisturbed run, survivors are bit-identical,
    and the pool invariants hold after every rollback."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    _, gold = _run(cfg, params, prompts)
    chaos = ChaosInjector([Fault("abort", step=2, rid=0),   # resident
                           Fault("abort", step=1, rid=3)])  # still queued
    eng, out = _run(cfg, params, prompts, chaos=chaos)
    reasons = {r.rid: r.finish_reason for r in eng.finished}
    assert reasons[0] == "aborted" and reasons[3] == "aborted"
    assert reasons[1] == "done" and reasons[2] == "done"
    assert out[1] == gold[1] and out[2] == gold[2]
    assert out[0] == gold[0][:len(out[0])] and len(out[0]) < len(gold[0])
    assert out[3] == []
    assert eng.stats()["aborted"] == 2
    assert chaos.exhausted
    eng._pool.check()
    assert all(not pages for pages in eng._pool.owned)


def test_abort_unknown_rid_returns_false():
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64)
    assert not eng.abort(99)
    assert eng.stats()["aborted"] == 0


def test_abort_with_tree_shared_prefix_pages():
    """Abort a request whose prefix pages are radix-shared: the pool
    invariants hold, the tree pages survive the abort, and a follow-up
    identical prompt still gets the prefix hit."""
    cfg, params = _setup()
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab, (32,), dtype=np.int32)
    tail = rng.integers(0, cfg.vocab, (4,), dtype=np.int32)
    eng = Engine(params, cfg, slots=2, max_seq=64)
    # seed the tree with the base prefix
    eng.submit(Request(rid=0, prompt=base.copy(), max_new_tokens=4))
    eng.run()
    tree_pages = set(eng.cm.tree.pages())
    assert tree_pages, "tree must hold the base prefix"
    # admit a sharer (maps the cached prefix read-only), then abort it
    victim = Request(rid=1, prompt=np.concatenate([base, tail]),
                     max_new_tokens=8)
    eng.submit(victim)
    eng.step()                         # resident, prefix mapped shared
    assert victim.prefix_hit_tokens == 32
    assert eng.abort(1)
    assert victim.finish_reason == "aborted"
    eng._pool.check()
    assert tree_pages <= set(eng.cm.tree.pages()), \
        "abort must not drop tree-shared pages"
    # the follow-up identical prompt still hits the cached prefix
    follow = Request(rid=2, prompt=np.concatenate([base, tail]),
                     max_new_tokens=8)
    eng.submit(follow)
    eng.run()
    assert follow.finish_reason == "done"
    assert follow.prefix_hit_tokens >= 32
    eng._pool.check()


def test_deadline_expires_queued_and_resident():
    cfg, params = _setup()
    prompts = _prompts(cfg, n=3)
    eng = Engine(params, cfg, slots=2, max_seq=64)
    rs = [Request(rid=i, prompt=p, max_new_tokens=8)
          for i, p in enumerate(prompts)]
    rs[2].deadline_s = 0.0             # expires before it can be admitted
    for r in rs:
        eng.submit(r)
    eng.step()                         # rs[0], rs[1] resident
    assert rs[2].finish_reason == "deadline" and rs[2].out_tokens == []
    rs[0].deadline_s = 1e-9            # now expire a RESIDENT request
    eng._has_deadlines = True
    eng.run()
    assert rs[0].finish_reason == "deadline"
    assert rs[1].finish_reason == "done" and len(rs[1].out_tokens) == 8
    assert eng.stats()["deadline_expired"] == 2
    eng._pool.check()


# -- admission validation / watchdog ----------------------------------------

def test_submit_rejects_invalid_prompts():
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64,
                 cache_manager=CacheConfig(page_size=16))
    rng = np.random.default_rng(0)
    bad = [
        (np.zeros((0,), np.int32), "empty prompt"),
        (rng.random((8,)).astype(np.float32), "integer-typed"),
        (np.array([0, cfg.vocab + 5], np.int32), "outside"),
        (rng.integers(0, cfg.vocab, (200,), dtype=np.int32), "max_seq"),
    ]
    for rid, (prompt, needle) in enumerate(bad):
        req = Request(rid=rid, prompt=prompt)
        eng.submit(req)
        assert req.finish_reason == "rejected", needle
        assert needle in req.error
    assert eng.stats()["rejected"] == len(bad)
    # the page-infeasibility guard itself (defensive: under any valid
    # geometry page_size divides max_seq, so the max_seq check above
    # fires first; the guard protects future geometries + the watchdog)
    assert "pages" in eng.cm.infeasible(10 * eng.max_seq)
    assert eng.cm.infeasible(8) is None
    # the engine still serves a valid wave afterwards
    ok = Request(rid=99, prompt=rng.integers(0, cfg.vocab, (10,),
                                             dtype=np.int32),
                 max_new_tokens=4)
    eng.submit(ok)
    eng.run()
    assert ok.finish_reason == "done" and len(ok.out_tokens) == 4
    eng._pool.check()


def test_watchdog_rejects_wedged_head_instead_of_deadlocking():
    """A never-admittable request that slipped past submit() validation
    (pushed straight into the scheduler) must be rejected by the
    quiescent-engine watchdog, not deadlock the queue behind it."""
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(1)
    wedge = Request(rid=0, prompt=rng.integers(0, cfg.vocab, (200,),
                                               dtype=np.int32),
                    arrival=0)
    ok = Request(rid=1, prompt=rng.integers(0, cfg.vocab, (10,),
                                            dtype=np.int32),
                 max_new_tokens=4, arrival=1)
    eng.scheduler.push(wedge)          # bypasses admission validation
    eng.scheduler.push(ok)
    eng.run()
    assert wedge.finish_reason == "rejected"
    assert ok.finish_reason == "done" and len(ok.out_tokens) == 4
    assert eng.stats()["rejected"] == 1


# -- failure isolation -------------------------------------------------------

def test_corrupt_readback_quarantines_one_request():
    cfg, params = _setup()
    prompts = _prompts(cfg)
    _, gold = _run(cfg, params, prompts)
    chaos = ChaosInjector([Fault("corrupt_readback", step=3, slot=1)])
    eng, out = _run(cfg, params, prompts, chaos=chaos)
    reasons = {r.rid: r.finish_reason for r in eng.finished}
    failed = [rid for rid, fr in reasons.items() if fr == "failed"]
    assert len(failed) == 1
    assert "corrupt readback" in next(r.error for r in eng.finished
                                      if r.rid == failed[0])
    for rid, fr in reasons.items():
        if fr == "done":
            assert out[rid] == gold[rid], "other slots must be untouched"
    assert eng.stats()["failed"] == 1 and chaos.exhausted
    eng._pool.check()


def test_device_fault_recovery_survivors_bit_identical():
    """The crash-consistency headline: quarantine the faulting slot,
    swap-restore the survivors, finish them bit-identical to an
    undisturbed run — then serve a second wave normally."""
    cfg, params = _setup()
    prompts = _prompts(cfg, n=4, length=20, seed=3)
    _, gold = _run(cfg, params, prompts)
    chaos = ChaosInjector([Fault("device_fault", step=4, slot=0)])
    eng = Engine(params, cfg, slots=2, max_seq=64, chaos=chaos)
    rs = [Request(rid=i, prompt=p.copy(), max_new_tokens=8)
          for i, p in enumerate(prompts)]
    for r in rs:
        eng.submit(r)
    eng.run()
    assert chaos.exhausted
    reasons = {r.rid: r.finish_reason for r in rs}
    assert sorted(reasons.values()) == ["done", "done", "done", "failed"]
    quarantined = next(rid for rid, fr in reasons.items()
                       if fr == "failed")
    for r in rs:
        if r.finish_reason == "done":
            assert list(r.out_tokens) == gold[r.rid], \
                f"survivor {r.rid} diverged after recovery"
            assert r.rid == quarantined or len(r.out_tokens) == 8
    s = eng.stats()
    assert s["recoveries"] == 1 and s["failed"] == 1
    eng._pool.check()
    assert all(not pages for pages in eng._pool.owned)
    # the recovered engine keeps serving: identical second wave
    eng2_out = {}
    for i, p in enumerate(prompts):
        r = Request(rid=100 + i, prompt=p.copy(), max_new_tokens=8)
        eng.submit(r)
        eng2_out[i] = r
    eng.run()
    for i, r in eng2_out.items():
        assert r.finish_reason == "done"
        assert list(r.out_tokens) == gold[i]
    eng._pool.check()


def test_device_fault_without_slot_uses_preemption_policy():
    cfg, params = _setup()
    prompts = _prompts(cfg, n=2)
    _, gold = _run(cfg, params, prompts)
    chaos = ChaosInjector([Fault("device_fault", step=3)])   # no slot
    eng, out = _run(cfg, params, prompts, chaos=chaos)
    reasons = {r.rid: r.finish_reason for r in eng.finished}
    # youngest-victim policy quarantines the later arrival (rid 1)
    assert reasons == {0: "done", 1: "failed"}
    assert out[0] == gold[0]
    eng._pool.check()


def test_pool_exhaustion_chaos_streams_unchanged():
    """Chaos page holds squeeze an oversubscribed pool: the preemption
    machinery absorbs the pressure and every stream stays bit-identical
    to the undisturbed oversubscribed run."""
    cfg, params = _setup()
    prompts = _prompts(cfg, n=4, length=24, seed=5)
    cm = CacheConfig(page_size=16, num_pages=6)
    _, gold = _run(cfg, params, prompts, max_new=16, cache_manager=cm)
    chaos = ChaosInjector([Fault("pool_exhaustion", step=2, pages=3,
                                 steps=6)])
    eng, out = _run(cfg, params, prompts, max_new=16, cache_manager=cm,
                    chaos=chaos)
    assert all(r.finish_reason == "done" for r in eng.finished)
    assert out == gold
    assert chaos.injected["pool_exhaustion"] == 1
    eng._pool.check()


def test_injected_device_fault_is_runtime_error():
    exc = InjectedDeviceFault("boom", slot=2)
    assert isinstance(exc, RuntimeError) and exc.slot == 2


def test_stall_fault_sleeps_and_deadline_catches_it():
    cfg, params = _setup()
    prompts = _prompts(cfg, n=2)
    chaos = ChaosInjector([Fault("stall", step=1, seconds=0.02)])
    eng = Engine(params, cfg, slots=2, max_seq=64, chaos=chaos)
    rs = [Request(rid=i, prompt=p, max_new_tokens=6, deadline_s=0.01)
          for i, p in enumerate(prompts)]
    for r in rs:
        eng.submit(r)
    eng.run()
    assert chaos.injected["stall"] == 1
    # the stall burned the whole budget: both requests expire
    assert all(r.finish_reason == "deadline" for r in rs)
    eng._pool.check()


# -- facade: no silent drops -------------------------------------------------

def test_stream_marks_stalled_requests_failed():
    """A stream whose engine stops making progress terminates EVERY
    request: leftovers are failed with terminal sentinel events instead
    of silently dropping after flush()."""
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (10,), dtype=np.int32)
               for _ in range(3)]
    events = list(llm.stream(prompts, max_new_tokens=8, max_steps=2))
    rids = {e.rid for e in events}
    terminal = {e.rid: e for e in events if e.done}
    assert len(rids) == 3 and len(terminal) == 3, \
        "every submitted request's stream must terminate"
    assert any(e.finish_reason == "failed" for e in terminal.values())
    assert llm.engine._pool.check() is None
    # the facade stays serviceable for the next wave
    outs = llm.generate(prompts, max_new_tokens=4)
    assert all(o.finish_reason == "done" for o in outs)


def test_generate_reports_failures_instead_of_raising():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64,
                    chaos=ChaosInjector([Fault("device_fault", step=3,
                                               slot=0)]))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, (12,), dtype=np.int32)
               for _ in range(2)]
    prompts.append(np.zeros((0,), np.int32))             # rejected
    outs = llm.generate(prompts, max_new_tokens=6)
    by_reason = sorted(o.finish_reason for o in outs)
    assert by_reason == ["done", "failed", "rejected"]
    assert all(o.error for o in outs if o.finish_reason != "done")


def test_facade_abort_mid_stream():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (10,), dtype=np.int32)
               for _ in range(2)]
    events = []
    it = llm.stream(prompts, max_new_tokens=8)
    first = next(it)
    assert llm.abort(first.rid)
    events = [first] + list(it)
    terminal = {e.rid: e for e in events if e.done}
    assert terminal[first.rid].finish_reason == "aborted"
    other = next(rid for rid in terminal if rid != first.rid)
    assert terminal[other].finish_reason == "done"
    assert llm.engine._pool.check() is None


# -- a program that cannot run is not a request's fault ----------------------

def _failing_step(llm, fail_on_call: int):
    """Swap the engine's step program for one that raises a plain
    RuntimeError (what XLA or Mosaic raise) on call ``fail_on_call``."""
    real, calls = llm.engine._step_fn, []

    def step(*args):
        calls.append(1)
        if len(calls) == fail_on_call:
            raise RuntimeError("program cannot run on this device")
        return real(*args)

    llm.engine._step_fn = step


def test_step_program_failing_on_first_dispatch_raises_from_generate():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    _failing_step(llm, fail_on_call=1)
    with pytest.raises(ProgramError) as info:
        llm.generate(_prompts(cfg, n=2), max_new_tokens=4)
    assert isinstance(info.value.__cause__, RuntimeError)
    assert not isinstance(info.value, RuntimeError)


def test_admit_bucket_failing_on_first_dispatch_raises_from_generate():
    # each prefill bucket is its own compiled program: the 16-token bucket
    # succeeding does not vouch for the 64-token one
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    real = llm.engine._admit_fn

    def admit(*args):
        if args[11].shape[0] == 64:      # the padded prompt
            raise RuntimeError("bucket 64 cannot run on this device")
        return real(*args)

    llm.engine._admit_fn = admit
    prompts = _prompts(cfg, n=1, length=12) + _prompts(cfg, n=1, length=40)
    with pytest.raises(ProgramError, match="bucket 64"):
        llm.generate(prompts, max_new_tokens=4)


def test_step_fault_after_first_success_stays_isolated():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    _failing_step(llm, fail_on_call=3)
    outs = llm.generate(_prompts(cfg, n=2), max_new_tokens=6)
    assert sorted(o.finish_reason for o in outs) == ["done", "failed"]
    assert llm.stats()["recoveries"] == 1


# -- chaos page seizure: allocator-level cross-validation --------------------

def test_seize_free_respects_pool_invariants():
    from repro.serving.paging import PagePool
    pool = PagePool(8, 16, 2, 4)
    pages = pool.seize_free(3)
    assert len(pages) == 3
    pool.check()
    assert pool.num_free == 5
    assert pool.alloc_n(0, 4) and not pool.alloc_n(1, 2)
    pool.check()
    pool.release_seized(pages)
    pool.check()
    assert pool.alloc_n(1, 2)
    pool.release(0)
    pool.release(1)
    pool.check()
    assert pool.num_free == 8
    # seizing more than available clips
    assert len(pool.seize_free(99)) == 8 and pool.num_free == 0
    pool.check()


def test_seize_release_random_churn():
    """Plain-random cross-validation of the chaos seize/release rules
    (mirrors the hypothesis state machine, which needs the hypothesis
    package): interleave seizes, allocations, shared mappings, and
    releases; check() must hold throughout."""
    from repro.serving.paging import PagePool
    rng = np.random.default_rng(11)
    pool = PagePool(12, 16, 3, 6)
    seized: list[int] = []
    for _ in range(600):
        op = rng.integers(0, 5)
        slot = int(rng.integers(0, 3))
        if op == 0:
            seized.extend(pool.seize_free(int(rng.integers(1, 4))))
        elif op == 1 and seized:
            k = int(rng.integers(1, len(seized) + 1))
            drop, seized[:] = seized[:k], seized[k:]
            pool.release_seized(drop)
        elif op == 2:
            pool.alloc_n(slot, int(rng.integers(1, 3)))
        elif op == 3:
            cands = [p for pages in pool.owned for p in pages]
            take = [p for p in dict.fromkeys(cands)
                    if p not in pool.owned[slot]][:2]
            if take and len(pool.owned[slot]) + len(take) \
                    <= pool.pages_per_slot:
                pool.map_shared(slot, take)
        else:
            pool.release(slot)
        pool.check()
