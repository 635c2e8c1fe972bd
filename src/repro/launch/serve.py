"""Serving driver over the layered API: ``LLMEngine.generate`` on top of
SamplingParams (greedy / temperature / top-k / top-p, per-request seed),
a pluggable scheduler (fcfs / priority / sjf), and the unified cache
manager (contiguous or paged KV with preemption for PAGED_OK families).

CPU-runnable (``configs.smoke`` widths unless ``--full``):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --requests 6 --slots 3 --max-new 8
    # the published widths (on a TPU; see chip_smoke.py):
    PYTHONPATH=src python -m repro.launch.serve --full --slots 8 \
        --max-seq 2048 --prompt-len 512 --max-new 32
    # non-greedy, seeded (reproducible):
    PYTHONPATH=src python -m repro.launch.serve --temperature 0.8 \
        --top-k 40 --top-p 0.95 --seed 7
    # priority admission over an oversubscribed paged pool:
    PYTHONPATH=src python -m repro.launch.serve --scheduler priority \
        --requests 8 --prompt-len 48 --max-new 24 --num-pages 12
    # chaos drill: abort rid 1 at step 2, inject a device fault at step
    # 5 (quarantine + swap-restore recovery), per-request deadline:
    PYTHONPATH=src python -m repro.launch.serve --requests 6 \
        --chaos "abort@2:rid=1,device_fault@5:slot=0" --deadline 30
    # tensor-parallel over a forced-host 4-device mesh (data=2, model=2);
    # streams are bit-identical to the single-device run (single-threaded
    # Eigen, as launch/mesh.force_host_devices sets it):
    XLA_FLAGS="--xla_force_host_platform_device_count=4 \
    --xla_cpu_multi_thread_eigen=false" \
        PYTHONPATH=src python -m repro.launch.serve --tp 2 --slots 4
    # speculative decoding (greedy only; streams bit-identical to the
    # target-only run, in fewer steps):
    PYTHONPATH=src python -m repro.launch.serve --spec ngram --spec-k 4
    PYTHONPATH=src python -m repro.launch.serve --spec draft_model
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import configs
from repro.launch import compile_cache
from repro.models import registry
from repro.serving import (ChaosInjector, LLMEngine, SamplingParams,
                           SpecConfig)
from repro.sharding import tp as tp_plan

_LIFECYCLE = ("aborted", "rejected", "failed", "deadline_expired",
              "recoveries")


def parse_chaos(spec: str):
    """Compact fault-plan syntax: ``kind@step[:k=v[;k=v...]],...`` —
    e.g. ``abort@2:rid=1,device_fault@5:slot=0,
    pool_exhaustion@8:pages=3;steps=4``."""
    from repro.reliability import Fault
    faults = []
    for part in spec.split(","):
        head, _, kv = part.strip().partition(":")
        kind, _, step = head.partition("@")
        extra = {}
        for item in filter(None, kv.split(";")):
            k, _, v = item.partition("=")
            extra[k.strip()] = float(v) if k.strip() == "seconds" \
                else int(v)
        faults.append(Fault(kind=kind.strip(), step=int(step), **extra))
    return faults


def _make_spec(spec: str, k: int, cfg, seed: int):
    """Resolve ``--spec/--spec-k`` into a ``SpecConfig``. The draft model
    is a shrunk same-arch sibling (half the layers, fresh init key) — a
    stand-in with the right shape of cost/accept tradeoff, the way
    qwen2-0.5b would draft for qwen3-8b in production."""
    if spec == "ngram":
        return SpecConfig(drafter="ngram", k=k)
    import dataclasses
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft",
                               n_layers=max(1, cfg.n_layers // 2))
    draft_params, _ = registry.init(dcfg, jax.random.PRNGKey(seed + 1))
    return SpecConfig(drafter="draft_model", k=k,
                      draft_params=draft_params, draft_cfg=dcfg)


def init_params(cfg, seed: int = 0, *, plan=None, dtype=None):
    """Random weights from ``seed``, made by one jitted program straight
    into their placement: the default device, or the shardings of a mesh
    ``plan`` (``repro.sharding.tp``) — nothing is built whole on one
    device first. They are unpermuted either way: the engine's
    ``tp.shard_params`` permutes the gate/up columns. ``dtype`` stores
    the weight matrices (ndim >= 2) in it, e.g. ``cfg.dtype``: the model
    casts every matrix to its compute dtype where it uses it, so serving
    streams do not change, and the weights take half float32's bytes."""
    def build(key):
        params, _ = registry.init(cfg, key)
        if plan is not None:
            # draw every matrix split over ``model``, whatever its final
            # placement: a replicated float32 draw of the stacked w_down
            # alone would take most of a chip
            params = jax.tree.map(_split_draw, params)
        if dtype is not None:
            params = jax.tree.map(
                lambda x: x.astype(dtype) if x.ndim >= 2 else x, params)
        return params

    def _split_draw(x):
        axes = [a for a in range(x.ndim) if x.shape[a] % plan.model == 0]
        if x.ndim < 2 or not axes:
            return x
        spec = [None] * x.ndim
        spec[max(axes, key=lambda a: x.shape[a])] = "model"
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(plan.mesh, PartitionSpec(*spec)))

    key = jax.random.PRNGKey(seed)
    if plan is None:
        return jax.jit(build)(key)
    shardings = tp_plan.param_shardings(jax.eval_shape(build, key), plan)
    return jax.jit(build, out_shardings=shardings)(key)


def run(*, arch: str = "qwen2-0.5b", smoke: bool = True, requests: int = 6,
        slots: int = 3, max_new: int = 8, max_seq: int = 128,
        prompt_len: int = 16, seed: int = 0, verbose: bool = True,
        page_size: int = 16, num_pages: int | None = None,
        prefix_cache: bool = True, scheduler: str = "fcfs",
        temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
        sampling_seed: int | None = None, deadline: float | None = None,
        chaos: str | None = None, tp: int | None = None,
        spec: str | None = None, spec_k: int = 4):
    cfg = configs.smoke(arch) if smoke else configs.get(arch)
    injector = ChaosInjector(parse_chaos(chaos)) if chaos else None
    mesh = plan = None
    if tp is not None:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(tp)
        if cfg.family == "dense":
            plan = tp_plan.make_plan(cfg, mesh, slots)
    params = init_params(cfg, seed, plan=plan)
    spec_cfg = _make_spec(spec, spec_k, cfg, seed) if spec else None
    llm = LLMEngine(params, cfg, slots=slots, max_seq=max_seq,
                    scheduler=scheduler, page_size=page_size,
                    num_pages=num_pages, prefix_cache=prefix_cache,
                    chaos=injector, mesh=mesh, spec=spec_cfg)
    sp = SamplingParams(temperature=temperature, top_k=top_k, top_p=top_p,
                        seed=sampling_seed)
    rng = np.random.default_rng(seed)
    prompts = []
    for _ in range(requests):
        n = int(rng.integers(4, prompt_len + 1))
        if cfg.frontend == "frames":
            prompts.append(rng.standard_normal((n, cfg.d_model))
                           .astype(np.float32))
        else:
            prompts.append(rng.integers(0, cfg.vocab, (n,), dtype=np.int32))
    # under non-FCFS schedulers, give the batch a deterministic priority
    # spread so the policy has something to reorder
    priorities = [rid % 3 for rid in range(requests)]
    t0 = time.perf_counter()
    outs = llm.generate(prompts, sp, max_new_tokens=max_new,
                        priorities=priorities, deadlines=deadline)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(o.tokens) for o in outs)
    if verbose:
        for o in outs:
            tail = "" if o.finish_reason == "done" else (
                f"  [{o.finish_reason}"
                + (f": {o.error}]" if o.error else "]"))
            print(f"req {o.rid}: prompt[{o.prompt_len}] -> {o.tokens}"
                  f"{tail}")
        s = llm.stats()
        ttfts = [o.ttft_s for o in outs if o.ttft_s is not None]
        mode = "greedy" if sp.greedy else (
            f"T={sp.temperature:g}"
            + (f",top_k={sp.top_k}" if sp.top_k else "")
            + (f",top_p={sp.top_p:g}" if sp.top_p < 1 else ""))
        print(f"{len(outs)} requests, {total_tokens} tokens in {dt:.2f}s "
              f"({total_tokens/dt:.1f} tok/s, continuous batching x{slots}, "
              f"ttft {np.mean(ttfts)*1e3:.0f}ms, {s['steps']} steps, "
              f"{s['prefill_compiles']} prefill compiles, "
              f"sampling={mode}, scheduler={s['scheduler']} "
              f"({s['sched_reorders']} reorders)")
        if "mesh" in s:
            m = s["mesh"]
            sharded = [k for k in ("heads_tp", "mlp_tp", "vocab_tp",
                                   "batch_dp") if m[k]] or ["replicated"]
            print(f"mesh: data={m['data']} x model={m['model']} "
                  f"({', '.join(sharded)}), {s['readbacks']} readbacks "
                  f"in {s['steps']} steps")
        if s["paged"]:
            print(f"paged pool: {s['num_pages']} pages x {s['page_size']} "
                  f"rows ({s['preempt_mode']} preemption) — "
                  f"{s['preemptions']} preemptions, "
                  f"peak {s['peak_pages_in_use']}/{s['num_pages']} pages, "
                  f"mean util {s['page_util_mean']:.0%}, "
                  f"frag {s['page_frag_mean']:.0%}")
        if s.get("spec_on"):
            print(f"spec decode: {s['spec_drafter']} drafter, "
                  f"k={s['spec_k']} — "
                  f"{s['accepted_per_step']:.2f} tokens/step, "
                  f"{s['accepted_tokens']}/{s['draft_tokens']} drafts "
                  f"accepted ({s['accept_rate']:.0%})")
        if s.get("prefix_cache"):
            print(f"prefix cache: {s['prefix_hit_tokens']}/"
                  f"{s['prefix_query_tokens']} prompt tokens served from "
                  f"the radix tree (hit rate {s['prefix_hit_rate']:.0%}), "
                  f"{s['cow_copies']} CoW copies, "
                  f"{s['tree_pages']} cached pages, "
                  f"{s['tree_evictions']} tree evictions")
        lc = {k: s[k] for k in _LIFECYCLE if s.get(k)}
        if lc or "chaos_injected" in s:
            bits = ", ".join(f"{k}={v}" for k, v in lc.items()) \
                or "every request finished clean"
            print(f"lifecycle: {bits}")
            if "chaos_injected" in s:
                fired = {k: v for k, v in s["chaos_injected"].items() if v}
                print(f"chaos: injected {fired or 'nothing'}, "
                      f"{s['chaos_relents']} relents")
    return outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--full", action="store_true",
                    help="serve the arch's published widths instead of "
                         "its configs.smoke reduction")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged-pool size; below slots*max_seq/page_size "
                         "oversubscribes (admission queues + preemption)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix prefix caching (on by default "
                         "for paged token-prompt families)")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "priority", "sjf"],
                    help="admission policy (requests carry rid%%3 "
                         "priorities so 'priority' visibly reorders)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax (default); >0 samples")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=None, dest="sampling_seed",
                    help="per-request sampling seed (default: request id, "
                         "so runs are reproducible but requests diverge)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request wall-clock budget in seconds "
                         "(finish_reason='deadline' on expiry)")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="step-indexed fault plan, e.g. "
                         "'abort@2:rid=1,device_fault@5:slot=0,"
                         "pool_exhaustion@8:pages=3;steps=4'")
    ap.add_argument("--tp", type=int, default=None, metavar="M",
                    help="model-parallel size: serve sharded over a "
                         "(devices/M, M) (data, model) mesh; streams "
                         "stay bit-identical to the single-device run")
    ap.add_argument("--spec", default=None,
                    choices=["ngram", "draft_model"],
                    help="speculative decoding drafter (greedy only; "
                         "'draft_model' drafts with a half-depth "
                         "same-arch sibling)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per decode step "
                         "(the fused verify scores k+1 positions)")
    args = ap.parse_args()
    compile_cache.configure()
    run(arch=args.arch, smoke=not args.full, requests=args.requests,
        slots=args.slots,
        max_new=args.max_new, max_seq=args.max_seq,
        prompt_len=args.prompt_len, page_size=args.page_size,
        num_pages=args.num_pages, prefix_cache=not args.no_prefix_cache,
        scheduler=args.scheduler,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        sampling_seed=args.sampling_seed, deadline=args.deadline,
        chaos=args.chaos, tp=args.tp, spec=args.spec, spec_k=args.spec_k)


if __name__ == "__main__":
    main()
