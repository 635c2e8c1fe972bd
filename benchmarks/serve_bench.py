"""Serving-throughput benchmark for the continuous-batching engine.

Runs the device-resident engine (and optionally the host-driven reference
engine) over several request mixes and reports, per (arch, mix, engine):

    tokens/s        end-to-end decode throughput (wall clock, includes
                    compiles — the reference engine's per-length prefill
                    retraces are part of what this benchmark measures)
    ttft_ms         mean time-to-first-token (submit -> first prefill token)
    steps           fused decode dispatches
    prefill_compiles  prefill retraces (bucketed: bounded by the pow2
                    bucket count; reference: one per unique prompt length)

Mixes: ``uniform_short`` (one short length), ``long_tail`` (mostly short,
a few near-window prompts), ``ragged_burst`` (8+ distinct lengths arriving
at once), ``oversubscribed`` (long prompts x long generations whose total
token demand exceeds a deliberately undersized page pool — the paged
engine must admit by actual token count, grow slots page-by-page, and
preempt/swap the youngest occupant when the pool runs dry; rows then also
report ``preemptions`` and page utilization/fragmentation), and
``priority_mix`` (a ragged batch carrying deterministic rid-derived
priorities, admitted by the PriorityScheduler — the row's exact
``sched_reorders`` counter pins the policy's behavior in the regression
gate; per-request streams still match the FCFS reference for
slot-independent families, which is what ``--check`` asserts on the dense
arch), and ``shared_prefix`` (16 requests whose prompts are staircase
cuts of one 256-token base — ~90% of prompt tokens are radix-tree hits
once warm, including one exact-duplicate prompt that forces a
copy-on-write; rows report ``prefix_hit_tokens`` / ``prefix_hit_rate`` /
``cow_copies``, a ``device-nocache`` twin row runs the same engine with
the tree disabled, ``streams_match_nocache`` asserts bit-identical
streams and ``warm_ttft_ms`` compares first-token latency over the warm
requests), and ``chaos_mix`` (8 valid requests plus 2 that admission
must reject, run under a step-indexed ``ChaosInjector`` plan — two
aborts, one injected device-step fault recovered via quarantine +
swap-restore, one 5-page pool seizure — against an oversubscribed pool;
the ``device-nochaos`` twin runs the identical engine without the
injector, ``survivors_match_nochaos`` asserts surviving streams are
bit-identical and aborted streams exact prefixes, and the
``aborted/rejected/failed/recoveries`` lifecycle counters are
exact-gated; no host-reference row — the reference engine predates fault
recovery), and ``spec_mix`` (a ragged batch decoded speculatively: a
self-draft ``draft_model`` drafter — draft params = target params, so
greedy proposals deterministically match the target and the acceptance
counters are golden-stable — with ``k=3``; the ``device-nospec`` twin
runs the identical engine target-only, ``streams_match_nospec`` asserts
bit-identical streams, and the exact-gated ``accepted_per_step`` /
``draft_tokens`` / ``accept_rate`` counters pin the fused verify
program's acceptance behavior; no host-reference row — the reference
engine IS target-only decoding, which the twin already covers without a
subprocess cold start). Wall times on this host are CPU numbers — a
functional serving benchmark, not a TPU projection.

Device rows are driven through the ``LLMEngine`` facade
(``generate(prompts, sampling_params)``); the host-driven reference rows
keep the raw submit/run loop that engine predates.

    PYTHONPATH=src python benchmarks/serve_bench.py                # bench
    PYTHONPATH=src python benchmarks/serve_bench.py --compare      # + ref
    PYTHONPATH=src python benchmarks/serve_bench.py --check \
        --check-golden --arch qwen2-0.5b --mixes ragged_burst      # CI

``--check`` asserts bit-identical token streams between the two engines;
``--check-golden`` additionally compares against the recorded golden
streams in ``benchmarks/golden/`` (``--record-golden`` refreshes them).
Both exit non-zero on divergence. ``benchmarks/run.py --json`` embeds the
rows under ``bench.json["serving"]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ART = os.path.join(os.path.dirname(__file__), "artifacts")
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
SERVE_JSON = os.path.join(ART, "serve.json")

DEFAULT_ARCHS = ("qwen2-0.5b", "olmoe-1b-7b")   # two model families
SLOTS, MAX_SEQ, MAX_NEW, SEED = 4, 128, 8, 0

# (phase label, wall seconds) timings accumulated across the run and
# printed by --durations — the receipts that twin-only scenarios (chaos/
# spec) and --skip-reference runs really do skip the reference subprocess
_DURATIONS: list = []


def _mix_lengths(mix: str, rng) -> list[int]:
    if mix == "uniform_short":
        return [8] * 12
    if mix == "long_tail":
        return [int(n) for n in rng.integers(5, 11, 10)] + [48, 64]
    if mix == "ragged_burst":
        # 8+ distinct lengths, all submitted up front
        lens = [int(n) for n in rng.integers(4, 41, 16)]
        while len(set(lens)) < 8:
            lens.append(int(rng.integers(4, 41)))
        return lens
    if mix == "oversubscribed":
        # long-prompt burst: total demand (prompt + generation rounded up
        # to whole pages) far exceeds OVERSUB_PAGES * PAGE_SIZE rows, so a
        # paged engine must oversubscribe and preempt
        return [int(n) for n in rng.integers(40, 81, 10)]
    if mix == "priority_mix":
        # ragged batch with rid-derived priorities (see build_requests):
        # the PriorityScheduler must reorder admission deterministically
        return [int(n) for n in rng.integers(6, 33, 12)]
    if mix == "shared_prefix":
        # lengths only (frames fallback); token prompts share content too
        return _SHARED_PREFIX_LENS
    if mix == "chaos_mix":
        # 8 valid requests plus two that admission must reject up front:
        # rid 8 is empty, rid 9 cannot fit max_seq (no room to emit)
        return [int(n) for n in rng.integers(20, 61, 8)] + [0, 200]
    if mix == "spec_mix":
        # ragged batch for speculative decoding: enough generation per
        # request (MIX_MAX_NEW) that variable acceptance spans many steps
        return [int(n) for n in rng.integers(6, 33, 10)]
    raise KeyError(f"unknown mix {mix!r}; have {sorted(MIXES)}")


MIXES = ("uniform_short", "long_tail", "ragged_burst", "oversubscribed",
         "priority_mix", "shared_prefix", "chaos_mix", "spec_mix")

# chaos_mix has no host-reference oracle: the reference engine predates
# admission validation and fault recovery, so its twin row is instead the
# SAME device engine run without the injector (see bench_arch). spec_mix
# likewise: the reference engine IS the target-only stream, and its
# device-nospec twin covers that comparison in-process — spinning up the
# reference subprocess for it would cold-start an oracle nobody consults
MIX_NO_REFERENCE = frozenset({"chaos_mix", "spec_mix"})

SPEC_K = 3      # spec_mix draft length (verify scores k+1 = 4 positions)

# paged-pool geometry for the oversubscribed mix: 4 slots x 128 max_seq
# would fully subscribe 32 pages of 16; 12 pages force admission queueing
# and mid-decode preemption (the contiguous fallback of non-PAGED_OK
# families simply ignores these knobs)
PAGE_SIZE, OVERSUB_PAGES = 16, 12
MIX_ENGINE_KW = {"oversubscribed": {"page_size": PAGE_SIZE,
                                    "num_pages": OVERSUB_PAGES},
                 "priority_mix": {"scheduler": "priority"},
                 # long staircase prompts over one 256-token base need the
                 # bigger window (240-token prompt + 8 generated < 256)
                 "shared_prefix": {"max_seq": 256},
                 # chaos runs against an oversubscribed pool so the
                 # injected page seizure actually induces preemption
                 "chaos_mix": {"page_size": PAGE_SIZE, "num_pages": 18}}
MIX_MAX_NEW = {"oversubscribed": 24, "chaos_mix": 12, "spec_mix": 16}


def _chaos_plan():
    """The deterministic chaos_mix fault plan, all step-indexed (never
    wall-clock) so the surviving streams and lifecycle counters are
    golden-stable: one mid-decode abort, one abort while likely still
    queued, a device-step fault recovered by quarantine + swap-restore,
    and a 4-step seizure of 5 pool pages (paged engines only; the
    contiguous fallback marks it fired without effect)."""
    from repro.reliability import Fault
    return [Fault("abort", step=2, rid=1),
            Fault("abort", step=5, rid=5),
            Fault("device_fault", step=7, slot=1),
            Fault("pool_exhaustion", step=10, pages=5, steps=4)]

# shared_prefix recipe: r0-r11 are a page-aligned staircase over one base
# (64, 80, ..., 240 — every suffix after the cached prefix is exactly one
# 16-row page), r12 duplicates r5 exactly (the forced-CoW shape: full-
# prompt match, last page copied before re-prefill), r13-r15 cut the base
# at a page boundary and append a ragged uncached tail. ~90% of all
# prompt tokens are radix-tree hits once the tree is warm.
_SHARED_PREFIX_STAIRS = [64 + 16 * i for i in range(12)]
_SHARED_PREFIX_TAILS = ((208, 5), (96, 9), (176, 3))
_SHARED_PREFIX_LENS = (_SHARED_PREFIX_STAIRS + [144]
                       + [cut + extra for cut, extra
                          in _SHARED_PREFIX_TAILS])


def _shared_prefix_prompts(cfg, rng) -> list[np.ndarray]:
    base = rng.integers(0, cfg.vocab, (256,), dtype=np.int32)
    prompts = [base[:n].copy() for n in _SHARED_PREFIX_STAIRS]
    prompts.append(base[:144].copy())           # exact duplicate of r5
    for cut, extra in _SHARED_PREFIX_TAILS:
        tail = rng.integers(0, cfg.vocab, (extra,), dtype=np.int32)
        prompts.append(np.concatenate([base[:cut], tail]))
    return prompts


def build_requests(cfg, mix: str, *, seed: int = SEED,
                   max_new: int = None):
    """Deterministic request list for (cfg, mix, seed)."""
    from repro.serving.engine import Request
    if max_new is None:
        max_new = MIX_MAX_NEW.get(mix, MAX_NEW)
    rng = np.random.default_rng(seed)
    if mix == "shared_prefix" and cfg.frontend != "frames":
        return [Request(rid=rid, prompt=p, max_new_tokens=max_new)
                for rid, p in enumerate(_shared_prefix_prompts(cfg, rng))]
    reqs = []
    for rid, n in enumerate(_mix_lengths(mix, rng)):
        if cfg.frontend == "frames":
            prompt = rng.standard_normal((n, cfg.d_model)).astype(np.float32)
        else:
            prompt = rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
        # deterministic rid-derived priority spread (only the priority
        # scheduler reads it; the field's presence cannot perturb FCFS)
        prio = (rid * 5) % 3 if mix == "priority_mix" else 0
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                            priority=prio))
    return reqs


def _metrics_row(wall, toks, ttfts, stats, streams) -> dict:
    row = {
        "requests": len(streams),
        "tokens": toks,
        "wall_s": wall,
        "tok_per_s": toks / wall if wall else 0.0,
        "ttft_ms": float(np.mean(ttfts)) * 1e3 if ttfts else None,
        "steps": stats.get("steps"),
        # one batched host readback per dispatched step — the property
        # the sharded engine must preserve (exact-gated; the host-driven
        # reference engine predates the counter and reports 0)
        "readbacks": stats.get("readbacks", 0),
        "prefill_compiles": stats.get("prefill_compiles"),
        "paged": stats.get("paged", False),
        "preemptions": stats.get("preemptions", 0),
        "streams": streams,
    }
    if "scheduler" in stats:
        row["scheduler"] = stats["scheduler"]
        row["sched_reorders"] = stats["sched_reorders"]
    # request-lifecycle counters (deterministic; exact-gated): nonzero
    # only under the chaos_mix injector or client aborts/deadlines
    for key in ("aborted", "rejected", "failed", "deadline_expired",
                "recoveries"):
        row[key] = stats.get(key, 0)
    # speculative-decoding counters, always present (zero when spec is
    # off or inert) — deterministic under greedy self-draft, so the
    # regression gate compares them exactly like the lifecycle counters
    row["spec_on"] = stats.get("spec_on", False)
    row["accepted_per_step"] = round(stats.get("accepted_per_step", 0.0), 4)
    row["accepted_tokens"] = stats.get("accepted_tokens", 0)
    row["draft_tokens"] = stats.get("draft_tokens", 0)
    row["accept_rate"] = round(stats.get("accept_rate", 0.0), 4)
    # always present (zero when caching is off/unsupported) so the
    # regression gate can compare them uniformly across engines
    row["prefix_cache"] = stats.get("prefix_cache", False)
    row["prefix_hit_tokens"] = stats.get("prefix_hit_tokens", 0)
    row["cow_copies"] = stats.get("cow_copies", 0)
    if row["prefix_cache"]:
        row["prefix_hit_rate"] = round(stats.get("prefix_hit_rate", 0.0), 4)
        row["tree_evictions"] = stats.get("tree_evictions", 0)
    if stats.get("paged"):
        row.update({
            "page_size": stats["page_size"],
            "num_pages": stats["num_pages"],
            "peak_pages_in_use": stats["peak_pages_in_use"],
            "page_util_mean": round(stats["page_util_mean"], 4),
            "page_frag_mean": round(stats["page_frag_mean"], 4),
        })
    return row


def run_engine(engine, requests) -> dict:
    """Drive one raw engine over pre-built Requests (the reference path)."""
    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r)
    done = engine.run()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    ttfts = [r.t_first - r.t_submit for r in done
             if getattr(r, "t_first", 0) and getattr(r, "t_submit", 0)]
    stats = engine.stats() if hasattr(engine, "stats") else {}
    return _metrics_row(wall, toks, ttfts, stats,
                        {r.rid: list(r.out_tokens) for r in done})


def run_llm(llm, requests) -> dict:
    """Drive the LLMEngine facade over the same request list (device
    path): prompts + per-request knobs in, RequestOutputs out — no
    submit/run/out_tokens scraping."""
    t0 = time.perf_counter()
    outs = llm.generate(
        [r.prompt for r in requests],
        max_new_tokens=[r.max_new_tokens for r in requests],
        priorities=[r.priority for r in requests])
    wall = time.perf_counter() - t0
    toks = sum(len(o.tokens) for o in outs)
    ttfts = [o.ttft_s for o in outs if o.ttft_s is not None]
    row = _metrics_row(wall, toks, ttfts, llm.stats(),
                       {o.rid: list(o.tokens) for o in outs})
    # per-request detail for the warm-TTFT comparison; popped by
    # bench_arch before rows leave the process
    row["_ttfts"] = {o.rid: o.ttft_s for o in outs}
    row["_hits"] = {o.rid: o.prefix_hit_tokens for o in outs}
    row["_reasons"] = {o.rid: o.finish_reason for o in outs}
    return row


def reference_rows(arch: str, mixes=MIXES, *, seed: int = SEED) -> list[dict]:
    """Measure the host-driven reference engine (run this in a FRESH
    process: in-process ordering would hand one engine the other's warm
    XLA op caches and skew the comparison either way)."""
    import jax
    from repro import configs
    from repro.models import registry
    from repro.serving.reference import ReferenceEngine

    cfg = configs.smoke(arch)
    params, _ = registry.init(cfg, jax.random.PRNGKey(seed))
    rows = []
    for mix in mixes:
        if mix in MIX_NO_REFERENCE:
            continue
        reqs = build_requests(cfg, mix, seed=seed)
        max_seq = MIX_ENGINE_KW.get(mix, {}).get("max_seq", MAX_SEQ)
        row = {"arch": arch, "mix": mix, "engine": "reference",
               **run_engine(ReferenceEngine(params, cfg, slots=SLOTS,
                                            max_seq=max_seq), reqs)}
        row["prefill_compiles"] = len({len(r.prompt) for r in reqs})
        rows.append(row)
    return rows


def _reference_rows_subprocess(arch: str, mixes, seed: int) -> list[dict]:
    """Cold, isolated reference measurement via a child interpreter."""
    import subprocess
    import sys
    import tempfile
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out = f.name
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--reference-only",
             "--out", out, "--arch", arch, "--mixes", ",".join(mixes)],
            env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"reference-engine subprocess failed (rc={proc.returncode})"
                f":\n{proc.stderr[-2000:]}")
        with open(out) as f:
            return json.load(f)
    finally:
        _DURATIONS.append((f"reference_subprocess/{arch}",
                           time.perf_counter() - t0))
        os.unlink(out)


def bench_arch(arch: str, mixes=MIXES, *, compare: bool = False,
               check: bool = False, seed: int = SEED) -> list[dict]:
    """All mixes for one arch; fresh engines share one param set."""
    import jax
    from repro import configs
    from repro.models import registry
    from repro.serving import LLMEngine

    cfg = configs.smoke(arch)
    params, _ = registry.init(cfg, jax.random.PRNGKey(seed))
    # per-request streams have an oracle (the FCFS reference, or the
    # chaos row's undisturbed twin) only when decode is slot-independent
    # (PAGED_OK): aborts/recoveries/reordering change pool composition,
    # which slot-coupled families (MoE capacity routing) observe
    slot_independent = bool(getattr(registry.module_for(cfg),
                                    "PAGED_OK", False))
    # tensor-parallel twin: the same engine sharded over a (2, 2)
    # (data, model) mesh — dense family only, and only when the forced
    # host platform actually yielded >= 4 devices. Streams must be
    # bit-identical to the single-device row (streams_match_sharded).
    sharded_mesh = None
    if cfg.family == "dense" and len(jax.devices()) >= 4:
        from repro.launch.mesh import make_mesh
        sharded_mesh = make_mesh((2, 2), ("data", "model"),
                                 devices=jax.devices()[:4])
    rows = []
    for mix in mixes:
        kw = dict(slots=SLOTS, max_seq=MAX_SEQ)
        kw.update(MIX_ENGINE_KW.get(mix, {}))
        chaos = None
        if mix == "chaos_mix":
            from repro.serving import ChaosInjector
            chaos = ChaosInjector(_chaos_plan())
        spec = None
        if mix == "spec_mix":
            # self-draft: the draft model IS the target, so every greedy
            # proposal matches and acceptance is deterministic (near
            # k+1 tokens/step) — the strongest golden-stable setting for
            # exact-gating the verify program. Inert (zero counters) for
            # non-paged families; the row still runs target-equivalent.
            from repro.serving import SpecConfig
            spec = SpecConfig(drafter="draft_model", k=SPEC_K,
                              draft_params=params, draft_cfg=cfg)
        llm = LLMEngine(params, cfg, chaos=chaos, spec=spec, **kw)
        reqs = build_requests(cfg, mix, seed=seed)
        row = {"arch": arch, "mix": mix, "engine": "device",
               **run_llm(llm, reqs)}
        rows.append(row)
        if mix == "spec_mix":
            # the spec row's oracle: the identical engine target-only —
            # greedy spec streams must be bitwise identical, just
            # reached in fewer (exact-gated) steps
            llm0 = LLMEngine(params, cfg, **kw)
            row0 = {"arch": arch, "mix": mix, "engine": "device-nospec",
                    **run_llm(llm0, reqs)}
            row["streams_match_nospec"] = row["streams"] == row0["streams"]
            rows.append(row0)
        if mix == "chaos_mix":
            assert chaos.exhausted, "chaos plan failed to fire fully"
            # the chaos row's oracle: the same engine, same requests, no
            # injector — surviving streams must be bit-identical and
            # aborted streams exact prefixes of the undisturbed run
            llm0 = LLMEngine(params, cfg, **kw)
            row0 = {"arch": arch, "mix": mix, "engine": "device-nochaos",
                    **run_llm(llm0, reqs)}
            if slot_independent:
                match = True
                for rid, stream in row["streams"].items():
                    want = row0["streams"].get(rid, [])
                    reason = row["_reasons"][rid]
                    if reason == "done":
                        match &= stream == want
                    elif reason == "aborted":
                        match &= stream == want[:len(stream)]
                row["survivors_match_nochaos"] = match
            else:
                row["survivors_match_nochaos"] = None   # no oracle
            rows.append(row0)
        if mix == "shared_prefix":
            # the prefix cache's own oracle: the identical engine with the
            # radix tree disabled — streams must match bit-for-bit, and
            # warm requests (those with tree hits) show the TTFT win
            llm0 = LLMEngine(params, cfg, prefix_cache=False, **kw)
            row0 = {"arch": arch, "mix": mix, "engine": "device-nocache",
                    **run_llm(llm0, reqs)}
            row["streams_match_nocache"] = \
                row["streams"] == row0["streams"]
            warm = sorted(r for r, h in row["_hits"].items() if h > 0)
            if warm:
                for r_ in (row, row0):
                    ts = [r_["_ttfts"][w] for w in warm
                          if r_["_ttfts"][w] is not None]
                    r_["warm_ttft_ms"] = float(np.mean(ts)) * 1e3 \
                        if ts else None
            rows.append(row0)
        if sharded_mesh is not None:
            # fresh injector for the chaos twin: the plan is stateful
            chaos_s = None
            if mix == "chaos_mix":
                from repro.serving import ChaosInjector
                chaos_s = ChaosInjector(_chaos_plan())
            llm_s = LLMEngine(params, cfg, chaos=chaos_s, spec=spec,
                              mesh=sharded_mesh, **kw)
            row_s = {"arch": arch, "mix": mix, "engine": "device-sharded",
                     **run_llm(llm_s, reqs)}
            row_s["streams_match_sharded"] = \
                row_s["streams"] == row["streams"]
            rows.append(row_s)
    for row in rows:
        row.pop("_ttfts", None)
        row.pop("_hits", None)
        row.pop("_reasons", None)
    if compare or check:
        ref_mixes = [m for m in mixes if m not in MIX_NO_REFERENCE]
        refs = {r["mix"]: r for r in
                _reference_rows_subprocess(arch, ref_mixes, seed)} \
            if ref_mixes else {}
        for row in list(rows):
            if row["engine"] != "device":
                continue
            ref = refs.get(row["mix"])
            if ref is None:            # no host oracle (chaos_mix)
                continue
            row["speedup_vs_reference"] = (ref["wall_s"] / row["wall_s"]
                                           if row["wall_s"] else None)
            sched = MIX_ENGINE_KW.get(row["mix"], {}).get("scheduler",
                                                          "fcfs")
            if sched != "fcfs" and not slot_independent:
                row["streams_match_reference"] = None   # no oracle
            else:
                row["streams_match_reference"] = (
                    {str(k): v for k, v in row["streams"].items()}
                    == {str(k): v for k, v in ref["streams"].items()})
            rows.append(ref)
    return rows


def _golden_path(arch: str, mix: str) -> str:
    return os.path.join(GOLDEN_DIR, f"serve_{arch}_{mix}.json")


def check_golden(rows, *, record: bool = False) -> bool:
    """Compare device-engine streams against the recorded goldens."""
    ok = True
    for row in rows:
        if row["engine"] != "device":
            continue
        path = _golden_path(row["arch"], row["mix"])
        streams = {str(k): v for k, v in row["streams"].items()}
        if record:
            os.makedirs(GOLDEN_DIR, exist_ok=True)
            with open(path, "w") as f:
                json.dump({"arch": row["arch"], "mix": row["mix"],
                           "seed": SEED, "slots": SLOTS, "max_seq": MAX_SEQ,
                           "max_new": MIX_MAX_NEW.get(row["mix"], MAX_NEW),
                           "engine_kw": MIX_ENGINE_KW.get(row["mix"], {}),
                           "streams": streams}, f,
                          indent=1, sort_keys=True)
            print(f"# golden recorded -> {path}")
            continue
        if not os.path.exists(path):
            # a missing golden must FAIL the check, not silently pass —
            # otherwise a renamed arch/mix (or uncommitted goldens) turns
            # the CI gate into a no-op
            ok = False
            print(f"# GOLDEN MISSING for {row['arch']}/{row['mix']}: {path} "
                  f"(run with --record-golden and commit it)")
            continue
        want = json.load(open(path))["streams"]
        if want != streams:
            ok = False
            bad = sorted(k for k in want if want[k] != streams.get(k))
            print(f"# GOLDEN MISMATCH {row['arch']}/{row['mix']}: "
                  f"rids {bad[:5]} diverge ({path})")
    return ok


def print_rows(rows):
    print("# Serving — continuous batching throughput "
          "(name,us_per_token,derived)")
    for r in rows:
        us = r["wall_s"] / max(r["tokens"], 1) * 1e6
        extra = ""
        if r.get("speedup_vs_reference") is not None:
            extra = (f",speedup={r['speedup_vs_reference']:.2f}x,"
                     f"match={r['streams_match_reference']}")
        ttft = f"{r['ttft_ms']:.0f}" if r.get("ttft_ms") is not None else "na"
        paged = ""
        if r.get("paged"):
            paged = (f",preempt={r['preemptions']},"
                     f"pages={r['peak_pages_in_use']}/{r['num_pages']},"
                     f"frag={r['page_frag_mean']:.2f}")
        sched = ""
        if r.get("scheduler") and r["scheduler"] != "fcfs":
            sched = (f",sched={r['scheduler']},"
                     f"reorders={r['sched_reorders']}")
        pfx = ""
        if r.get("prefix_cache"):
            pfx = (f",hit_rate={r['prefix_hit_rate']:.2f},"
                   f"hit_tokens={r['prefix_hit_tokens']},"
                   f"cow={r['cow_copies']}")
        if r.get("warm_ttft_ms") is not None:
            pfx += f",warm_ttft_ms={r['warm_ttft_ms']:.0f}"
        if r.get("streams_match_nocache") is not None:
            pfx += f",match_nocache={r['streams_match_nocache']}"
        if r.get("streams_match_sharded") is not None:
            pfx += f",match_sharded={r['streams_match_sharded']}"
        if r.get("spec_on"):
            pfx += (f",accepted_per_step={r['accepted_per_step']:.2f},"
                    f"accept_rate={r['accept_rate']:.2f},"
                    f"draft_tokens={r['draft_tokens']}")
        if r.get("streams_match_nospec") is not None:
            pfx += f",match_nospec={r['streams_match_nospec']}"
        if any(r.get(k) for k in ("aborted", "rejected", "failed",
                                  "deadline_expired", "recoveries")):
            pfx += (f",aborted={r['aborted']},rejected={r['rejected']},"
                    f"failed={r['failed']},recoveries={r['recoveries']}")
        if "survivors_match_nochaos" in r:
            pfx += f",survivors_match={r['survivors_match_nochaos']}"
        print(f"serving/{r['arch']}/{r['mix']}/{r['engine']},{us:.0f},"
              f"tok_s={r['tok_per_s']:.1f},ttft_ms={ttft},"
              f"steps={r['steps']},"
              f"prefill_compiles={r['prefill_compiles']}{sched}{pfx}"
              f"{paged}{extra}")


def bench(archs=DEFAULT_ARCHS, mixes=MIXES, *, compare: bool = False,
          check: bool = False, seed: int = SEED) -> list[dict]:
    rows = []
    for arch in archs:
        t0 = time.perf_counter()
        rows.extend(bench_arch(arch, mixes, compare=compare, check=check,
                               seed=seed))
        _DURATIONS.append((f"bench_arch/{arch}",
                           time.perf_counter() - t0))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", action="append", dest="archs", default=None)
    ap.add_argument("--mixes", default=",".join(MIXES),
                    help="comma-separated subset of " + ",".join(MIXES))
    ap.add_argument("--compare", action="store_true",
                    help="also run the host-driven reference engine")
    ap.add_argument("--check", action="store_true",
                    help="fail unless device streams are bit-identical to "
                         "the reference engine")
    ap.add_argument("--check-golden", action="store_true",
                    help="fail unless device streams match the recorded "
                         "goldens in benchmarks/golden/")
    ap.add_argument("--record-golden", action="store_true")
    ap.add_argument("--skip-reference", action="store_true",
                    help="skip the host-reference subprocess (fast local "
                         "runs; disables --compare rows and --check's "
                         "stream comparison, golden checks still run)")
    ap.add_argument("--durations", action="store_true",
                    help="print per-phase wall timings (device rows per "
                         "arch, reference subprocesses) — shows what "
                         "--skip-reference and the twin-only mixes save")
    ap.add_argument("--json", action="store_true",
                    help=f"write rows (sans streams) to {SERVE_JSON}")
    ap.add_argument("--reference-only", action="store_true",
                    help=argparse.SUPPRESS)   # internal: cold child process
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    # The sharded serving rows need >= 4 devices: split the host platform
    # before jax starts its backend. Device rows are unaffected: single-
    # device engines run on device 0, whose computation is identical with
    # or without the virtual split.
    import jax
    from repro.launch import compile_cache
    from repro.launch.mesh import force_host_devices
    force_host_devices(4)
    compile_cache.configure()
    compare = (args.compare or args.check) and not args.skip_reference
    if compare and jax.default_backend() == "tpu":
        # the reference rows run in a child interpreter, and a chip
        # belongs to the one process that already holds it
        ap.error("--compare/--check start a reference subprocess, which "
                 "cannot share the TPU this process holds; pass "
                 "--skip-reference on a TPU")

    mixes = tuple(m for m in args.mixes.split(",") if m)
    if args.reference_only:
        rows = []
        for arch in tuple(args.archs or DEFAULT_ARCHS):
            rows.extend(reference_rows(arch, mixes))
        with open(args.out, "w") as f:
            json.dump(rows, f)
        return 0
    rows = bench(tuple(args.archs or DEFAULT_ARCHS), mixes,
                 compare=compare, check=args.check and not
                 args.skip_reference)
    print_rows(rows)
    if args.durations:
        print("# durations (phase,wall_s)")
        for label, secs in _DURATIONS:
            print(f"# {label},{secs:.2f}")
        if not any(lbl.startswith("reference_subprocess")
                   for lbl, _ in _DURATIONS):
            print("# (no reference subprocess was started)")
    rc = 0
    if args.check:
        # None = no FCFS oracle (reordering scheduler on a slot-coupled
        # family) — skipped, not failed
        bad = [r for r in rows if r["engine"] == "device"
               and r.get("streams_match_reference") is False]
        for r in bad:
            print(f"# STREAM MISMATCH vs reference: "
                  f"{r['arch']}/{r['mix']}")
        rc |= bool(bad)
    if args.check or args.check_golden:
        # sharded rows must be bit-identical to the single-device rows
        bad_s = [r for r in rows
                 if r.get("streams_match_sharded") is False]
        for r in bad_s:
            print(f"# STREAM MISMATCH sharded vs single-device: "
                  f"{r['arch']}/{r['mix']}")
        rc |= bool(bad_s)
        # greedy speculative streams must be bitwise identical to the
        # target-only twin — a drafter may be slow, never wrong
        bad_sp = [r for r in rows
                  if r.get("streams_match_nospec") is False]
        for r in bad_sp:
            print(f"# STREAM MISMATCH spec vs target-only: "
                  f"{r['arch']}/{r['mix']}")
        rc |= bool(bad_sp)
    if args.check_golden or args.record_golden:
        rc |= not check_golden(rows, record=args.record_golden)
    if args.json:
        os.makedirs(ART, exist_ok=True)
        slim = [{k: v for k, v in r.items() if k != "streams"}
                for r in rows]
        with open(SERVE_JSON, "w") as f:
            json.dump(slim, f, indent=1)
        print(f"# serving json -> {SERVE_JSON}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
