"""Bit-identity harness: sharded serving vs the single-device engine.

Runs the same request waves through two engines in one process — one
single-device (``mesh=None``), one sharded over a forced-host CPU mesh —
and asserts the token streams are **bitwise identical** and the
deterministic counters (steps, preemptions, prefix hits, CoW copies,
recoveries) agree. This is the executable proof behind the sharded
serving design in ``docs/ARCHITECTURE.md``: every cross-device exchange
is an all-gather, so sharding must not change a single token. The
single-device engine gets the plain ``registry.init`` tree; the sharded
one gets the same weights built straight into their mesh shardings by
``repro.launch.serve.init_params``: weights placed from one device and
weights built on the mesh must give the same streams.

Scenarios:
    greedy     argmax decoding, continuous batching
    sampling   seeded temperature/top-k/top-p sampling
    preempt    oversubscribed paged pool forcing swap preemption
    prefix     radix prefix-cache hits across two request waves
    chaos      injected device fault + swap-restore recovery

Usage:
    python tools/sharded_check.py --arch qwen3-8b --mesh 2,2 --json
    python tools/sharded_check.py --arch qwen2-0.5b --mesh 1,4
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_SRC = Path(__file__).resolve().parents[1] / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.mesh import force_host_devices, make_mesh  # noqa: E402
from repro.launch.serve import init_params  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.reliability import Fault  # noqa: E402
from repro.serving import (ChaosInjector, LLMEngine,  # noqa: E402
                           SamplingParams)
from repro.sharding import tp  # noqa: E402

SCENARIOS = ("greedy", "sampling", "preempt", "prefix", "chaos")

# deterministic counters that must agree between the two engines
COMPARE = ("steps", "readbacks", "prefill_compiles", "preemptions",
           "sched_reorders", "prefix_hit_tokens", "cow_copies",
           "recoveries", "aborted", "failed")


SLOTS = 4


def _prompts(cfg, rng, n, lo=4, hi=16):
    return [rng.integers(0, cfg.vocab, (int(rng.integers(lo, hi + 1)),),
                         dtype=np.int32) for _ in range(n)]


def _streams(outs):
    return [(o.rid, o.finish_reason, list(map(int, o.tokens)))
            for o in outs]


def run_scenario(name: str, cfg, params, mesh):
    """One engine, one scenario; returns (streams, stats)."""
    kw = dict(slots=SLOTS, max_seq=128)
    chaos = None
    if name == "chaos":
        chaos = ChaosInjector([Fault(kind="device_fault", step=7, slot=1)])
    if name == "preempt":
        kw.update(max_seq=96, num_pages=10)
    llm = LLMEngine(params, cfg, mesh=mesh, chaos=chaos, **kw)
    rng = np.random.default_rng(0)
    sp = None
    if name == "sampling":
        sp = SamplingParams(temperature=0.8, top_k=5, top_p=0.9)
    if name == "prefix":
        # wave 1 caches the base prompt's pages in the radix tree; wave 2
        # shares a 32-token (2-page) prefix and must hit it
        base = rng.integers(0, cfg.vocab, (48,), dtype=np.int32)
        streams = _streams(llm.generate([base], sp, max_new_tokens=8))
        tails = [rng.integers(0, cfg.vocab, (6,), dtype=np.int32)
                 for _ in range(3)]
        wave2 = [np.concatenate([base[:32], t]) for t in tails]
        streams += _streams(llm.generate(wave2, sp, max_new_tokens=8))
        return streams, llm.stats()
    if name == "preempt":
        prompts = _prompts(cfg, rng, 6, lo=24, hi=40)
        outs = llm.generate(prompts, sp, max_new_tokens=16)
    else:
        prompts = _prompts(cfg, rng, 6)
        outs = llm.generate(prompts, sp, max_new_tokens=8)
    return _streams(outs), llm.stats()


def check(arch: str, mesh_shape, scenarios=SCENARIOS) -> dict:
    """Run every scenario twice (single-device, sharded) and compare."""
    cfg = configs.smoke(arch)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(mesh_shape, ("data", "model"))
    sh_params = init_params(cfg, 0, plan=tp.make_plan(cfg, mesh, SLOTS))
    report = {"arch": arch, "mesh": list(mesh_shape), "scenarios": {},
              "ok": True}
    for name in scenarios:
        base_streams, base_stats = run_scenario(name, cfg, params, None)
        sh_streams, sh_stats = run_scenario(name, cfg, sh_params, mesh)
        report.setdefault("plan", sh_stats.get("mesh"))
        notes = []
        if base_streams != sh_streams:
            notes.append("token streams differ")
        for k in COMPARE:
            if base_stats.get(k, 0) != sh_stats.get(k, 0):
                notes.append(f"{k}: single={base_stats.get(k, 0)} "
                             f"sharded={sh_stats.get(k, 0)}")
        for label, s in (("single", base_stats), ("sharded", sh_stats)):
            if s["readbacks"] != s["steps"]:
                notes.append(f"{label}: {s['readbacks']} readbacks != "
                             f"{s['steps']} steps")
        if name == "preempt" and base_stats.get("preemptions", 0) == 0:
            notes.append("scenario forced no preemption")
        if name == "prefix" and base_stats.get("prefix_hit_tokens", 0) == 0:
            notes.append("scenario produced no prefix-cache hit")
        if name == "chaos" and base_stats.get("recoveries", 0) != 1:
            notes.append(f"expected 1 recovery, got "
                         f"{base_stats.get('recoveries', 0)}")
        ok = not notes
        report["scenarios"][name] = {
            "ok": ok, "streams_match": base_streams == sh_streams,
            "steps": base_stats["steps"],
            "counters": {k: base_stats.get(k, 0) for k in COMPARE},
            "notes": notes}
        report["ok"] = report["ok"] and ok
    return report


def main():
    ap = argparse.ArgumentParser(
        description="sharded-vs-single-device bit-identity check")
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--mesh", default="2,2",
                    help="data,model axis sizes (e.g. 2,2 or 1,4)")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count")
    ap.add_argument("--scenarios", default=None,
                    help=f"comma list from {','.join(SCENARIOS)}")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args()
    force_host_devices(args.devices)
    mesh_shape = tuple(int(x) for x in args.mesh.split(","))
    scenarios = tuple(args.scenarios.split(",")) if args.scenarios \
        else SCENARIOS
    report = check(args.arch, mesh_shape, scenarios)
    if args.as_json:
        print(json.dumps(report, indent=2))
    else:
        print(f"{args.arch} on mesh {mesh_shape} plan={report.get('plan')}")
        for name, r in report["scenarios"].items():
            mark = "ok" if r["ok"] else "FAIL " + "; ".join(r["notes"])
            print(f"  {name:<10} streams_match={r['streams_match']} "
                  f"steps={r['steps']} -> {mark}")
        print("bit-identical" if report["ok"] else "MISMATCH")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
