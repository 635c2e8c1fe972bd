"""Layered serving-API tests: SamplingParams validation + the fused
on-device draw, seeded-stream determinism (across engine restarts, across
contiguous vs paged cache managers, and under swap preemption), scheduler
policies (FCFS / priority / SJF) with their exact reorder counters, the
LLMEngine generate/stream facade, the deprecation shims for the old
Engine kwargs, and the one-batched-readback-per-step invariant for
non-greedy decode (sampling must add zero extra host syncs)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.serving import (CacheConfig, LLMEngine, Request, SamplingParams)
from repro.serving.engine import Engine
from repro.serving.sampling import sample_tokens

_PARAMS = {}


def _setup(arch="qwen2-0.5b"):
    if arch not in _PARAMS:
        cfg = configs.smoke(arch)
        _PARAMS[arch] = (cfg, registry.init(cfg, jax.random.PRNGKey(0))[0])
    return _PARAMS[arch]


def _requests(cfg, lens, *, max_new=4, seed=0, sampling=None, prios=None):
    rng = np.random.default_rng(seed)
    out = []
    for rid, n in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                           sampling=sampling,
                           priority=prios[rid] if prios else 0))
    return out


def _streams(eng, cfg, lens, **kw):
    for r in _requests(cfg, lens, **kw):
        eng.submit(r)
    done = eng.run()
    return {r.rid: list(r.out_tokens) for r in done}


# ---------------------------------------------------------------------------
# SamplingParams + the draw itself
# ---------------------------------------------------------------------------

def test_sampling_params_validation():
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.7).greedy
    assert SamplingParams(seed=None).resolve_seed(5) == 5
    assert SamplingParams(seed=9).resolve_seed(5) == 9
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)


def test_sample_tokens_reduces_to_argmax():
    """temperature=0, top_k=1, and a tiny top_p must all pick the argmax
    token; draws stay inside the top-k set; (key, index) determinism."""
    rng = np.random.default_rng(0)
    lg = jnp.asarray(rng.standard_normal((6, 64)).astype(np.float32))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(6)])
    idx = jnp.arange(6, dtype=jnp.int32)
    ones = jnp.ones((6,))
    zeros_i = jnp.zeros((6,), jnp.int32)
    argmax = np.asarray(jnp.argmax(lg, -1))

    greedy = sample_tokens(lg, keys, idx, jnp.zeros((6,)), zeros_i, ones)
    np.testing.assert_array_equal(np.asarray(greedy), argmax)
    top1 = sample_tokens(lg, keys, idx, 2.0 * ones,
                         jnp.full((6,), 1, jnp.int32), ones)
    np.testing.assert_array_equal(np.asarray(top1), argmax)
    nucleus = sample_tokens(lg, keys, idx, 2.0 * ones, zeros_i,
                            jnp.full((6,), 1e-9))
    np.testing.assert_array_equal(np.asarray(nucleus), argmax)

    k = 5
    topk = sample_tokens(lg, keys, idx, 5.0 * ones,
                         jnp.full((6,), k, jnp.int32), ones)
    order = np.argsort(-np.asarray(lg), axis=-1)
    for b, t in enumerate(np.asarray(topk)):
        assert t in order[b, :k]

    again = sample_tokens(lg, keys, idx, 5.0 * ones,
                          jnp.full((6,), k, jnp.int32), ones)
    np.testing.assert_array_equal(np.asarray(topk), np.asarray(again))
    other = sample_tokens(lg, keys, idx + 1, 5.0 * ones,
                          jnp.full((6,), k, jnp.int32), ones)
    assert (np.asarray(topk) != np.asarray(other)).any()


ROWS, VOCAB = 64, 4096
_draw = jax.jit(sample_tokens)


def _oracle(lg, keys, index, temp, top_k, top_p):
    """``sample_tokens``'s documented rule in NumPy, masses in float64:
    stable descending order, the top-k prefix, top-p on the preceding
    mass, Gumbel-argmax with the noise drawn in vocab order (ties to the
    lowest id). Asserts that no preceding mass lies within float32
    rounding of ``top_p``, so that the case's answer is well defined."""
    lg = np.asarray(lg.astype(jnp.float32))
    out = []
    for b in range(lg.shape[0]):
        if temp[b] <= 0.0:
            out.append(int(np.argmax(lg[b])))
            continue
        scaled = lg[b] / np.maximum(np.float32(temp[b]), np.float32(1e-6))
        kept = np.argsort(-scaled, kind="stable")[:top_k[b] or VOCAB]
        s = scaled[kept].astype(np.float64)
        probs = np.exp(s - s.max())
        probs /= probs.sum()
        prev = np.cumsum(probs) - probs
        # at top_p 1.0 only a tail whose preceding mass rounds up to 1
        # can differ, and it holds far too little mass to be drawn
        margin = np.abs(prev[1:] - top_p[b]).min(initial=1.0)
        assert top_p[b] >= 1.0 or margin > 2e-6
        kept = kept[prev < top_p[b]]
        g = np.asarray(jax.random.gumbel(
            jax.random.fold_in(keys[b], index[b]), (VOCAB,)))
        score = scaled[kept] + g[kept]
        out.append(int(kept[score == score.max()].min()))
    return np.asarray(out)


def _tied_rows(rng, n_rows, values, tail):
    """bf16 rows whose leading entries take ``values`` (equal values are
    exact ties) at random vocab ids, the rest ``tail`` below them."""
    lg = np.full((n_rows, VOCAB), tail, np.float32)
    lg += 0.25 * rng.standard_normal((n_rows, VOCAB)).astype(np.float32)
    for row in lg:
        row[rng.choice(VOCAB, len(values), replace=False)] = values
    return lg


def _sampling_case(name):
    """(bf16 logits, temperature, top_k, top_p) of one equivalence case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    lg = 3.0 * rng.standard_normal((ROWS, VOCAB)).astype(np.float32)
    temp = np.full(ROWS, 0.8, np.float32)
    k = np.zeros(ROWS, np.int32)
    p = np.ones(ROWS, np.float32)
    if name == "greedy":
        temp[:] = 0.0
        k[:], p[:] = 1, 1e-9          # ignored by greedy rows
        lg[:, [70, 3000]] = lg.max() + 1.0    # exact tie at the argmax
    elif name.startswith("top_k"):
        k[:] = int(name[5:])
    elif name.startswith("top_p"):
        p[:] = float(name[5:])
    elif name == "tie_at_top_k":
        # k=1 rows: two tokens tie for the top; k=20 rows: 21 tie, so
        # the 20th and 21st places are a tie across the cut
        half = ROWS // 2
        lg[:half] = _tied_rows(rng, half, [3.0, 3.0], -4.0)
        lg[half:] = _tied_rows(rng, ROWS - half, [3.0] * 21, -4.0)
        temp[:], k[:half], k[half:] = 1.0, 1, 20
    elif name == "tie_at_top_p":
        # masses 0.55, 0.30, then a tied pair of 0.07 each: the first of
        # the pair (lower id) starts below 0.9, the second above it
        lg = _tied_rows(rng, ROWS, np.log([0.55, 0.30, 0.07, 0.07]), -13.0)
        temp[:], p[:] = 1.0, 0.9
    elif name == "mixed":
        temp = rng.choice([0.0, 0.5, 0.8, 1.3], ROWS).astype(np.float32)
        k = rng.choice([0, 1, 20], ROWS).astype(np.int32)
        p = rng.choice([1.0, 0.9, 1e-9], ROWS).astype(np.float32)
    return jnp.asarray(lg).astype(jnp.bfloat16), temp, k, p


@pytest.mark.parametrize("name", [
    "greedy", "top_k0", "top_k1", "top_k20", "top_p1.0", "top_p0.9",
    "top_p1e-9", "tie_at_top_k", "tie_at_top_p", "mixed"])
def test_sample_tokens_matches_numpy_oracle(name):
    """Every row draws the token the documented rule gives, bf16 ties
    at the top-k and top-p cuts included."""
    lg, temp, k, p = _sampling_case(name)
    keys = jnp.stack([jax.random.PRNGKey(1000 + r) for r in range(ROWS)])
    index = jnp.arange(ROWS, dtype=jnp.int32) * 7
    got = _draw(lg, keys, index, jnp.asarray(temp), jnp.asarray(k),
                jnp.asarray(p))
    np.testing.assert_array_equal(
        np.asarray(got), _oracle(lg, np.asarray(keys), np.asarray(index),
                                 temp, k, p))


def test_sample_tokens_compiles_to_one_sort_and_no_gather():
    """At the served shape (32 slots over qwen2's 151,936-entry vocab,
    bf16) the compiled draw holds one sort and no gather: truncation and
    draw run in sorted order, and an element-wise gather over the vocab
    is slow on the TPU."""
    S = jax.ShapeDtypeStruct
    b, v = 32, 151936
    hlo = jax.jit(sample_tokens).lower(
        S((b, v), jnp.bfloat16), S((b, 2), jnp.uint32), S((b,), jnp.int32),
        S((b,), jnp.float32), S((b,), jnp.int32), S((b,), jnp.float32),
    ).compile().as_text()
    assert len(re.findall(r"(?<=\s)gather\(", hlo)) == 0
    assert len(re.findall(r"(?<=\s)sort\(", hlo)) == 1


# ---------------------------------------------------------------------------
# seeded determinism end to end
# ---------------------------------------------------------------------------

LENS = [3, 5, 7, 9, 11, 4]
SP = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, seed=7)


def test_seeded_streams_deterministic_across_restarts_and_managers():
    """Same seed => identical non-greedy streams from a fresh engine
    (restart) AND across the contiguous vs paged cache managers; a
    different seed diverges; greedy differs from sampled."""
    cfg, params = _setup()
    a = _streams(Engine(params, cfg, slots=3, max_seq=64, sampling=SP),
                 cfg, LENS)
    b = _streams(Engine(params, cfg, slots=3, max_seq=64, sampling=SP),
                 cfg, LENS)
    assert a == b, "engine restart changed seeded streams"
    contig = _streams(
        Engine(params, cfg, slots=3, max_seq=64, sampling=SP,
               cache_manager=CacheConfig(paged=False)), cfg, LENS)
    assert a == contig, "cache-manager layout changed seeded streams"
    other = _streams(
        Engine(params, cfg, slots=3, max_seq=64,
               sampling=SamplingParams(temperature=0.8, top_k=20,
                                       top_p=0.95, seed=8)), cfg, LENS)
    assert a != other, "different seeds must diverge"
    greedy = _streams(Engine(params, cfg, slots=3, max_seq=64), cfg, LENS)
    assert a != greedy


def test_seeded_streams_survive_swap_preemption():
    """Non-greedy + oversubscribed pool: swap preemption restores the key
    state byte-for-byte, so the preempted streams equal the
    never-preempted contiguous streams token for token."""
    cfg, params = _setup()
    lens = [30, 25, 28, 21, 26]
    eng = Engine(params, cfg, slots=3, max_seq=64, sampling=SP,
                 cache_manager=CacheConfig(page_size=16, num_pages=6))
    preempted = _streams(eng, cfg, lens, max_new=20)
    assert eng.stats()["preemptions"] >= 1
    plain = _streams(
        Engine(params, cfg, slots=3, max_seq=64, sampling=SP,
               cache_manager=CacheConfig(paged=False)),
        cfg, lens, max_new=20)
    assert preempted == plain
    eng._pool.check()


# ---------------------------------------------------------------------------
# schedulers
# ---------------------------------------------------------------------------

def test_priority_scheduler_orders_admission():
    """slots=1 serializes the pool, so completion order IS admission
    order: highest priority first, FCFS within a level; the reorder
    counter is exact."""
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=1, max_seq=64, scheduler="priority")
    _streams(eng, cfg, [4, 4, 4], max_new=2, prios=[0, 2, 1])
    assert [r.rid for r in eng.finished] == [1, 2, 0]
    st = eng.stats()
    assert st["scheduler"] == "priority"
    assert st["sched_reorders"] == 2        # rid1 before 0, rid2 before 0
    assert st["sched_admitted"] == 3


def test_sjf_scheduler_orders_by_job_size():
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=1, max_seq=64, scheduler="sjf")
    reqs = _requests(cfg, [12, 4, 8], max_new=2)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert [r.rid for r in eng.finished] == [1, 2, 0]
    assert eng.stats()["scheduler"] == "sjf"


def test_sorted_scheduler_pops_by_identity():
    """Two waiting requests may share a rid (the engine never enforces
    uniqueness): pop must remove by identity, not dataclass equality —
    comparing the numpy prompt fields raises 'ambiguous truth value'."""
    from repro.serving.scheduler import PriorityScheduler
    sched = PriorityScheduler()
    a = Request(rid=0, prompt=np.array([1, 2, 3], np.int32), arrival=0)
    b = Request(rid=0, prompt=np.array([4, 5, 6], np.int32), arrival=1)
    sched.push(a)
    sched.push(b)
    assert sched.pop() is a and sched.pop() is b and len(sched) == 0


def test_greedy_engine_flips_to_sampling_step_on_demand():
    """A greedy-default engine runs the specialized argmax step until the
    first non-greedy request arrives, then retraces once and serves both
    kinds in the same pool."""
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64)
    assert eng._greedy_only
    reqs = _requests(cfg, [5, 6], max_new=3)
    reqs[1].sampling = SP
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert not eng._greedy_only
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(len(r.out_tokens) == 3 for r in done)


def test_fcfs_never_reorders():
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64)
    _streams(eng, cfg, [4, 6, 5, 7], max_new=2)
    st = eng.stats()
    assert st["scheduler"] == "fcfs"
    assert st["sched_reorders"] == 0
    with pytest.raises(ValueError):
        Engine(params, cfg, scheduler="lifo")


# ---------------------------------------------------------------------------
# LLMEngine facade
# ---------------------------------------------------------------------------

def test_llm_engine_generate_and_stream_agree():
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in [4, 7, 5]]
    outs = LLMEngine(params, cfg, slots=2, max_seq=64).generate(
        prompts, SP, max_new_tokens=4)
    assert [o.rid for o in outs] == [0, 1, 2]
    assert all(len(o.tokens) == 4 for o in outs)
    assert all(o.ttft_s is not None and o.ttft_s >= 0 for o in outs)

    events = list(LLMEngine(params, cfg, slots=2, max_seq=64).stream(
        prompts, SP, max_new_tokens=4))
    by_rid = {}
    for ev in events:
        assert ev.index == len(by_rid.setdefault(ev.rid, []))
        by_rid[ev.rid].append(ev.token)
    assert by_rid == {o.rid: o.tokens for o in outs}
    for rid, toks in by_rid.items():
        fin = [ev for ev in events if ev.rid == rid and ev.done]
        assert len(fin) == 1 and fin[0].index == len(toks) - 1


def test_llm_engine_rejects_mismatched_batch_args():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (4,), dtype=np.int32)
               for _ in range(3)]
    with pytest.raises(ValueError):
        llm.generate(prompts, [SP])                     # 1 params, 3 prompts
    with pytest.raises(ValueError):
        llm.generate(prompts, max_new_tokens=[4, 4])    # short list
    with pytest.raises(ValueError):
        llm.generate(prompts, priorities=[1])           # short list


def test_llm_engine_serves_successive_waves():
    cfg, params = _setup()
    llm = LLMEngine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(1)
    p = [rng.integers(0, cfg.vocab, (5,), dtype=np.int32)]
    first = llm.generate(p, max_new_tokens=3)
    second = llm.generate(p, max_new_tokens=3)
    assert first[0].rid == 0 and second[0].rid == 1
    assert first[0].tokens == second[0].tokens      # same greedy prompt
    # the facade prunes completed waves — a long-lived LLMEngine must not
    # retain every prompt ever served
    assert llm.engine.finished == []


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_old_engine_kwargs_warn_but_work():
    cfg, params = _setup()
    with pytest.warns(DeprecationWarning):
        eng = Engine(params, cfg, slots=2, max_seq=64, greedy=True)
    assert eng.default_sampling.greedy
    with pytest.warns(DeprecationWarning):
        eng = Engine(params, cfg, slots=2, max_seq=64, greedy=False)
    assert not eng.default_sampling.greedy          # no NotImplementedError
    with pytest.warns(DeprecationWarning):
        eng = Engine(params, cfg, slots=2, max_seq=64, preempt="recompute")
    assert eng.preempt_mode == "recompute"
    with pytest.warns(DeprecationWarning):
        eng = Engine(params, cfg, slots=2, max_seq=64, page_size=16,
                     num_pages=6)
    assert eng.paged and eng.num_pages == 6
    with pytest.raises(ValueError):
        Engine(params, cfg, slots=2, max_seq=64, preemption="drop")


def test_deprecated_greedy_false_produces_sampled_stream():
    cfg, params = _setup()
    with pytest.warns(DeprecationWarning):
        eng = Engine(params, cfg, slots=2, max_seq=64, greedy=False)
    sampled = _streams(eng, cfg, [5, 6], max_new=3)
    greedy = _streams(Engine(params, cfg, slots=2, max_seq=64), cfg,
                      [5, 6], max_new=3)
    assert sorted(sampled) == sorted(greedy)
    assert all(len(v) == 3 for v in sampled.values())


# ---------------------------------------------------------------------------
# non-greedy hot path: still one batched readback per step
# ---------------------------------------------------------------------------

def test_nongreedy_keeps_overlapped_single_readback():
    """Sampling is fused into the donated step: the host applies exactly
    one batched emit per dispatched step (plus nothing extra), and the
    readback of step k stays in flight while step k+1 dispatches."""
    cfg, params = _setup()
    eng = Engine(params, cfg, slots=2, max_seq=64, sampling=SP)
    applies = {"n": 0}
    orig = Engine._apply

    def counting_apply(self, pending):
        applies["n"] += 1
        return orig(self, pending)

    Engine._apply = counting_apply
    try:
        for r in _requests(cfg, [5, 6], max_new=6):
            eng.submit(r)
        overlapped = 0
        while eng.has_work():
            if not eng.step():
                break
            if eng._pending is not None:
                overlapped += 1         # emit still in flight post-dispatch
        eng.flush()
    finally:
        Engine._apply = orig
    assert len(eng.finished) == 2
    assert all(len(r.out_tokens) == 6 for r in eng.finished)
    # one batched apply per dispatched step — sampling added none
    assert applies["n"] == eng.stats()["steps"]
    assert overlapped == eng.stats()["steps"]
