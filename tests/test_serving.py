"""Serving-engine tests: bit-identical token streams vs the host-driven
(pre-refactor) reference engine, slot recycling under ragged admission,
the pow2 prefill retrace bound, paged-pool serving (full subscription,
oversubscription with swap preemption + requeue, recompute mode, per-family
gating), and an engine smoke across all five model families (whose cache
layouts all differ — the scatter is axes-driven)."""

import math

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.serving.cache_manager import CacheConfig
from repro.serving.engine import Engine, Request
from repro.serving.reference import ReferenceEngine

FAMILY_ARCHS = {
    "dense": "qwen2-0.5b",
    "moe": "olmoe-1b-7b",
    "xlstm": "xlstm-1.3b",
    "hybrid": "recurrentgemma-2b",
    "encdec": "seamless-m4t-large-v2",
}

_PARAMS = {}


def _setup(arch):
    if arch not in _PARAMS:
        cfg = configs.smoke(arch)
        _PARAMS[arch] = (cfg, registry.init(cfg, jax.random.PRNGKey(0))[0])
    return _PARAMS[arch]


def _requests(cfg, lens, *, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for rid, n in enumerate(lens):
        if cfg.frontend == "frames":
            prompt = rng.standard_normal((n, cfg.d_model)).astype(np.float32)
        else:
            prompt = rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return out


def _streams(engine_cls, cfg, params, lens, **kw):
    eng = engine_cls(params, cfg, slots=kw.pop("slots", 3),
                     max_seq=kw.pop("max_seq", 64))
    for r in _requests(cfg, lens, **kw):
        eng.submit(r)
    done = eng.run()
    return {r.rid: list(r.out_tokens) for r in done}, eng


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "olmoe-1b-7b"])
def test_bit_identical_streams(arch):
    """Device-resident engine == host-driven engine, token for token, on a
    fixed ragged mix — covering the bucketed-pad prefill path (dense) and
    the exact-length path with slot-coupled MoE routing."""
    cfg, params = _setup(arch)
    lens = [3, 5, 7, 9, 11, 4, 6, 13] if cfg.family == "dense" \
        else [4, 6, 9, 5, 7]
    new, _ = _streams(Engine, cfg, params, lens)
    ref, _ = _streams(ReferenceEngine, cfg, params, lens)
    assert new == ref
    assert len(new) == len(lens)


def test_max_seq_stop_matches_reference():
    """The on-device max-seq stop condition fires at the same token index
    as the host engine's."""
    cfg, params = _setup("qwen2-0.5b")
    new, _ = _streams(Engine, cfg, params, [4, 6], max_new=1000,
                      slots=2, max_seq=16)
    ref, _ = _streams(ReferenceEngine, cfg, params, [4, 6], max_new=1000,
                      slots=2, max_seq=16)
    assert new == ref
    assert all(len(v) > 1 for v in new.values())


def test_slot_recycling_ragged():
    """More requests than slots with ragged prompt lengths: every request
    completes with exactly max_new tokens through recycled slots."""
    cfg, params = _setup("qwen2-0.5b")
    lens = [3, 9, 5, 12, 4, 7, 15, 6, 10]
    new, eng = _streams(Engine, cfg, params, lens, max_new=4, slots=2)
    assert sorted(new) == list(range(len(lens)))
    assert all(len(v) == 4 for v in new.values())
    assert all(0 <= t < cfg.vocab for v in new.values() for t in v)
    assert not eng.queue and all(s.req is None for s in eng.slots)


def test_prefill_retrace_bound():
    """8+ distinct prompt lengths must trigger no more prefill compiles
    than the number of pow2 buckets (<= log2(max_seq)+1), strictly fewer
    than the per-unique-length behavior of the host engine."""
    cfg, params = _setup("qwen2-0.5b")
    lens = [3, 4, 5, 7, 9, 12, 17, 23, 29, 31]
    assert len(set(lens)) >= 8
    max_seq = 64
    new, eng = _streams(Engine, cfg, params, lens, max_new=3, slots=3,
                        max_seq=max_seq)
    stats = eng.stats()
    buckets = {1 << max(0, (n - 1).bit_length()) for n in lens}
    assert stats["pad_prefill"]
    assert stats["prefill_compiles"] <= len(buckets)
    assert stats["prefill_compiles"] <= int(math.log2(max_seq)) + 1
    assert stats["prefill_compiles"] < len(set(lens))
    assert len(new) == len(lens)


def test_paged_matches_contiguous_pool():
    """The paged pool (full subscription, no preemption) is a pure layout
    change: token streams equal the contiguous engine's bit-for-bit."""
    cfg, params = _setup("qwen2-0.5b")
    lens = [3, 9, 5, 12, 7]
    paged, eng = _streams(Engine, cfg, params, lens, max_new=4)
    contig, ceng = _streams(
        lambda p, c, **kw: Engine(p, c,
                                  cache_manager=CacheConfig(paged=False),
                                  **kw),
        cfg, params, lens, max_new=4)
    assert eng.stats()["paged"] and not ceng.stats()["paged"]
    assert paged == contig
    assert eng.stats()["preemptions"] == 0


@pytest.mark.parametrize("pallas", [False, True])
def test_free_slot_attends_nothing(pallas, monkeypatch):
    """A slot that holds no request decodes with length 0: its ``pos``,
    which keeps counting, may drift past ``max_seq`` without changing
    the active slots' tokens — through the jnp reference and through the
    Pallas kernel (interpret mode). ``attn_pages_walked_share`` counts the
    pages the active slots' lengths span."""
    from repro.kernels import ops
    if pallas:
        monkeypatch.setattr(ops, "_use_pallas",
                            lambda impl: (impl != "ref", True))
    cfg, params = _setup("qwen2-0.5b")
    lens, n_steps, page, max_seq = [5, 20], 3, 16, 64

    def serve(drift):
        eng = Engine(params, cfg, slots=3, max_seq=max_seq,
                     cache_manager=CacheConfig(page_size=page))
        for r in _requests(cfg, lens, max_new=12):
            eng.submit(r)
        eng.step()                              # admits both: slot 2 free
        if drift:
            eng._pos = eng._pos.at[2].set(10 * max_seq)
        for _ in range(n_steps - 1):
            eng.step()
        share = eng.stats()["attn_pages_walked_share"]
        return {r.rid: list(r.out_tokens) for r in eng.run()}, share

    drifted, share = serve(drift=True)
    ref, _ = _streams(ReferenceEngine, cfg, params, lens, max_new=12,
                      slots=3, max_seq=max_seq)
    assert drifted == serve(drift=False)[0] == ref
    assert all(len(t) == 12 for t in drifted.values())
    # lengths 5 + 3 and 20 + 3 span 1 and 2 of 3 slots x 4 pages
    assert share == (1 + 2) / (3 * max_seq // page)


def test_oversubscribed_bit_identical_with_preemption():
    """Oversubscribed pool (requests x lengths > capacity): the engine must
    preempt at least once, swap the victims back in, and still produce
    token streams bit-identical to the never-evicting reference engine."""
    cfg, params = _setup("qwen2-0.5b")
    lens = [30, 25, 28, 21, 26]          # ~130 prompt rows + generation
    kw = dict(max_new=20, slots=3, max_seq=64)
    new, eng = _streams(
        lambda p, c, **k: Engine(
            p, c, cache_manager=CacheConfig(page_size=16, num_pages=6),
            **k),
        cfg, params, lens, **dict(kw))
    ref, _ = _streams(ReferenceEngine, cfg, params, lens, **dict(kw))
    st = eng.stats()
    assert st["paged"] and st["preemptions"] >= 1
    assert st["peak_pages_in_use"] <= 6
    assert new == ref
    eng._pool.check()


def test_forced_preemption_requeue_roundtrip():
    """Minimum-size pool (one full-length slot) under long generations:
    every admission fights for pages, so requests are evicted and swapped
    back repeatedly — streams must survive multiple preemptions of the
    SAME request unchanged."""
    cfg, params = _setup("qwen2-0.5b")
    lens = [20, 17, 23]
    kw = dict(max_new=30, slots=3, max_seq=64)
    new, eng = _streams(
        lambda p, c, **k: Engine(
            p, c, cache_manager=CacheConfig(page_size=16, num_pages=4),
            **k),
        cfg, params, lens, **dict(kw))
    ref, _ = _streams(ReferenceEngine, cfg, params, lens, **dict(kw))
    assert new == ref
    assert eng.stats()["preemptions"] >= 2
    assert max(r.preemptions for r in eng.finished) >= 1
    assert all(r.done for r in eng.finished)
    eng._pool.check()
    # finished requests must release all slot mappings; only radix-cached
    # pages (pinned by the tree alone) may outlive their request
    assert all(not pages for pages in eng._pool.owned), \
        "finished requests must unmap their pages"
    tree_pages = eng.cm.tree.n_pages if eng.cm.prefix_cache else 0
    assert eng._pool.pages_in_use == tree_pages


def test_recompute_preemption_completes():
    """vLLM-style recompute preemption (drop pages, re-prefill the prompt +
    generated prefix): requests complete with exactly the reference token
    counts and keep their pre-eviction prefix. (Token values are only
    greedy-stable, not bit-guaranteed — that is what swap mode is for.)"""
    cfg, params = _setup("qwen2-0.5b")
    lens = [22, 19, 26]
    kw = dict(max_new=25, slots=3, max_seq=64)
    new, eng = _streams(
        lambda p, c, **k: Engine(
            p, c, cache_manager=CacheConfig(page_size=16, num_pages=4),
            preemption="recompute", **k),
        cfg, params, lens, **dict(kw))
    ref, _ = _streams(ReferenceEngine, cfg, params, lens, **dict(kw))
    assert eng.stats()["preemptions"] >= 1
    assert sorted(new) == sorted(ref)
    assert all(len(new[k]) == len(ref[k]) for k in ref)
    eng._pool.check()


def test_paged_gating_per_family():
    """Only PAGED_OK families without a rolling window page; forcing
    paged=True elsewhere is an error, and auto mode falls back."""
    cfg_moe, params_moe = _setup("olmoe-1b-7b")
    assert not registry.paged_ok(cfg_moe)
    eng = Engine(params_moe, cfg_moe, slots=2, max_seq=64)
    assert not eng.stats()["paged"]
    with pytest.raises(ValueError):
        Engine(params_moe, cfg_moe, slots=2, max_seq=64,
               cache_manager=CacheConfig(paged=True))
    cfg_q, params_q = _setup("qwen2-0.5b")
    assert registry.paged_ok(cfg_q)
    with pytest.raises(ValueError):   # page size must tile max_seq
        Engine(params_q, cfg_q, slots=2, max_seq=64,
               cache_manager=CacheConfig(page_size=24))


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_engine_smoke_all_families(family):
    """Admission scatter + pooled decode across every cache layout:
    positional KV (dense), exact-prefill KV (moe), stacked recurrent
    states (xlstm), mixed KV/recurrent/conv (hybrid), dual self+cross KV
    (encdec)."""
    cfg, params = _setup(FAMILY_ARCHS[family])
    new, eng = _streams(Engine, cfg, params, [5, 8, 6], max_new=3, slots=2)
    assert sorted(new) == [0, 1, 2]
    assert all(len(v) == 3 for v in new.values())
    assert all(0 <= t < cfg.vocab for v in new.values() for t in v)
    assert eng.stats()["steps"] > 0
