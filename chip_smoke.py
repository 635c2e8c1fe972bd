#!/usr/bin/env python3
"""Bring-up smoke run of the serving engine on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip: qwen2-0.5b at its published widths (24 layers, d_model 896,
14/2 heads, d_ff 4864, vocab 151936) with random weights from ``--seed``,
served through ``LLMEngine``: paged pool of 16-row pages, 8 slots,
``max_seq`` 2048, prefix cache on. Eight greedy requests with prompts of
65-1024 tokens (three pow2 prefill buckets) and one ``temperature=0.8``
request, 32 new tokens each. It fails unless every request finishes
``done`` with 32 tokens, the engine counts no failure and no recovery, and
the decode program it served with holds a Pallas kernel
(``tpu_custom_call``). Then each main-path kernel runs at the served
shapes against ``repro.kernels.ref`` within the kernel tests' bf16
tolerance.

Four chips (``--four-chips``, this phase only): qwen3-8b at published
widths, (a) cut to 4 layers and served on one chip and on a (1, 4)
``(data, model)`` mesh, whose greedy streams must be identical; (b) all
36 layers on the mesh, answering the same requests. Weights are built
straight into their shardings and stored in bf16 (every use casts them
to bf16 anyway, so streams do not change): 36 float32 layers would not
fit four 16 GB chips under the all-gather plan, which replicates ``wo``,
``w_down`` and the embedding.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
The last line of a passing run is one JSON object naming the device.
Times printed are smoke timings of one cold run, compilation included —
not metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

PAGE_SIZE, SLOTS, MAX_SEQ, MAX_NEW = 16, 8, 2048, 32
# one prompt per entry, its length drawn from (bucket/2, bucket]
BUCKETS = (128, 128, 128, 512, 512, 512, 1024, 1024)
SAMPLED_BUCKET = 512
# the kernel tests' bf16 tolerance: |out - ref| <= ATOL + RTOL * |ref|
ATOL = RTOL = 3e-2


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def tpu_devices():
    """JAX's devices, or exit non-zero naming the platform found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        sys.exit(2)
    return devs


def check_outputs(outs, llm, vocab: int, max_new: int, label: str):
    """Every request done with ``max_new`` in-vocab tokens; no failure or
    recovery in the engine's counters."""
    for o in outs:
        if o.finish_reason != "done" or len(o.tokens) != max_new:
            fail(f"{label}: request {o.rid} ended {o.finish_reason!r} with "
                 f"{len(o.tokens)}/{max_new} tokens ({o.error})")
        if not all(0 <= t < vocab for t in o.tokens):
            fail(f"{label}: request {o.rid} emitted a token outside "
                 f"[0, {vocab})")
    s = llm.stats()
    if s["failed"] or s["recoveries"]:
        fail(f"{label}: engine counted failed={s['failed']} "
             f"recoveries={s['recoveries']}")
    n = llm.engine.step_program_text().count("tpu_custom_call")
    if not n:
        fail(f"{label}: the served decode program has no tpu_custom_call "
             "(the jnp references ran, not the Pallas kernels)")
    print(f"{label}: {len(outs)} requests done, {s['steps']} decode steps, "
          f"{s['prefill_compiles']} prefill compiles, failed=0 "
          f"recoveries=0, tpu_custom_call x{n} in the decode program")
    return s


def param_bytes_on(params, device) -> int:
    import jax
    return sum(sh.data.nbytes for x in jax.tree.leaves(params)
               for sh in x.addressable_shards if sh.device == device)


# -- one chip -----------------------------------------------------------------

def serve_one_chip(seed: int, device) -> None:
    import jax
    from repro import configs
    from repro.launch.serve import init_params
    from repro.serving import LLMEngine, SamplingParams

    cfg = configs.get("qwen2-0.5b")
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} compute={cfg.dtype}")
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    llm = LLMEngine(params, cfg, slots=SLOTS, max_seq=MAX_SEQ,
                    page_size=PAGE_SIZE, prefix_cache=True)
    jax.block_until_ready((params, llm.engine.cache))
    setup = time.perf_counter() - t0
    pool = sum(x.nbytes for x in jax.tree.leaves(llm.engine.cache))
    print(f"weights: {param_bytes_on(params, device) / 2**30:.3f} GiB "
          f"float32; KV pool: {llm.engine.num_pages} pages x {PAGE_SIZE} "
          f"rows, {pool / 2**30:.3f} GiB; set-up {setup:.1f} s (smoke "
          "timing)")

    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(b // 2 + 1, b + 1))
            for b in BUCKETS + (SAMPLED_BUCKET,)]
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in lens]
    sampling = [SamplingParams()] * len(BUCKETS) \
        + [SamplingParams(temperature=0.8, seed=seed)]
    print(f"load: prompt lengths {lens}; {MAX_NEW} new tokens each; "
          f"{len(BUCKETS)} greedy + 1 at temperature 0.8")
    t0 = time.perf_counter()
    outs = llm.generate(prompts, sampling, max_new_tokens=MAX_NEW)
    wall = time.perf_counter() - t0
    check_outputs(outs, llm, cfg.vocab, MAX_NEW, "serve")
    print(f"serve: {wall:.1f} s for {sum(len(o.tokens) for o in outs)} "
          "tokens (smoke timing, compilation included; not a metric)")
    del llm, params
    gc.collect()
    check_kernels(cfg, seed)


def check_kernels(cfg, seed: int) -> None:
    """Each main-path kernel at the served shapes, Pallas on the chip
    against ``repro.kernels.ref``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    dt = cfg.jnp_dtype
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n_pt = MAX_SEQ // PAGE_SIZE
    n_pages = SLOTS * n_pt + 1                   # + the trap page
    kv = (n_pages, PAGE_SIZE, cfg.n_kv_heads, cfg.head_dim)
    table = (1 + jax.random.permutation(ks[3], n_pages - 1)) \
        .reshape(SLOTS, n_pt).astype(jnp.int32)
    cases = {
        "paged_flash_decode": (ops.paged_flash_decode_attention, (
            jax.random.normal(ks[0], (SLOTS, cfg.n_heads, cfg.head_dim), dt),
            jax.random.normal(ks[1], kv, dt),
            jax.random.normal(ks[2], kv, dt), table),
            {"kv_len": jax.random.randint(ks[4], (SLOTS,), 1, MAX_SEQ + 1)}),
        "fused_add_rmsnorm": (ops.fused_add_rmsnorm, (
            jax.random.normal(ks[5], (SLOTS, 1, cfg.d_model), dt),
            jax.random.normal(ks[6], (SLOTS, 1, cfg.d_model), dt),
            1.0 + 0.1 * jax.random.normal(ks[7], (cfg.d_model,))),
            {"eps": cfg.norm_eps}),
        "silu_and_mul": (ops.silu_and_mul, (
            2.0 * jax.random.normal(ks[0], (SLOTS, 1, 2 * cfg.d_ff), dt),),
            {}),
    }
    for name, (op, args, kw) in cases.items():
        pallas = jax.jit(functools.partial(op, impl="pallas", **kw))
        if "tpu_custom_call" not in pallas.lower(*args).as_text():
            fail(f"kernel {name}: impl='pallas' lowered without a "
                 "tpu_custom_call")
        got = jax.tree.leaves(pallas(*args))
        want = jax.tree.leaves(
            jax.jit(functools.partial(op, impl="ref", **kw))(*args))
        err = excess = 0.0
        for g, w in zip(got, want):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            if not bool(jnp.all(jnp.isfinite(g))):
                fail(f"kernel {name}: non-finite output")
            d = jnp.abs(g - w)
            err = max(err, float(jnp.max(d)))
            excess = max(excess,
                         float(jnp.max(d - (ATOL + RTOL * jnp.abs(w)))))
        shapes = ", ".join(str(tuple(a.shape)) for a in args
                           if hasattr(a, "shape"))
        ok = excess <= 0.0
        print(f"kernel {name} [{shapes}] {dt.name}: max |pallas - ref| = "
              f"{err:.3e}; tolerance |err| <= {ATOL} + {RTOL}*|ref| -> "
              f"{'ok' if ok else 'EXCEEDED'}")
        if not ok:
            fail(f"kernel {name} exceeds its tolerance")


# -- four chips ---------------------------------------------------------------

def serve_four_chips(seed: int, devs) -> None:
    import jax
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import init_params
    from repro.serving import LLMEngine
    from repro.sharding import tp

    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    full = configs.get("qwen3-8b")
    cut = dataclasses.replace(full, n_layers=4)
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    kw = dict(slots=4, max_seq=512, page_size=PAGE_SIZE, prefix_cache=True)
    max_new = 16
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, full.vocab, (int(rng.integers(65, 257)),),
                            dtype=np.int32) for _ in range(6)]
    print(f"model: {full.name} d_model={full.d_model} "
          f"heads={full.n_heads}/{full.n_kv_heads} d_ff={full.d_ff} "
          f"vocab={full.vocab}, weights stored in {full.dtype}; "
          f"{len(prompts)} greedy requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {max_new} new tokens each")

    def serve(cfg, on_mesh, label):
        plan = tp.make_plan(cfg, mesh, kw["slots"]) if on_mesh else None
        t0 = time.perf_counter()
        # no reference kept to the unpermuted tree: on the mesh the engine
        # places its own permuted copy of the gate/up matrix
        llm = LLMEngine(init_params(cfg, seed, plan=plan,
                                    dtype=cfg.jnp_dtype),
                        cfg, mesh=mesh if on_mesh else None, **kw)
        params = llm.engine.params
        outs = llm.generate(prompts, max_new_tokens=max_new)
        wall = time.perf_counter() - t0
        s = check_outputs(outs, llm, cfg.vocab, max_new, label)
        where = f"mesh {s['mesh']}" if on_mesh else "one chip"
        print(f"{label}: {cfg.n_layers} layers on {where}; weights on "
              f"device 0: {param_bytes_on(params, devs[0]) / 2**30:.3f} "
              f"GiB; {wall:.1f} s (smoke timing, compilation included)")
        return [list(o.tokens) for o in outs], params, plan

    one, one_params, _ = serve(cut, False, "(a) one chip")
    sharded, mesh_params, plan = serve(cut, True, "(a) mesh")
    if one != sharded:
        rid = next(i for i, (a, b) in enumerate(zip(one, sharded)) if a != b)
        at = next(i for i, (a, b) in enumerate(zip(one[rid], sharded[rid]))
                  if a != b)
        tokens = np.concatenate([prompts[rid],
                                 np.asarray(one[rid][:at], np.int32)])
        gap = logit_gap(cut, one_params, mesh_params, plan, tokens)
        fail(f"(a) streams differ: request {rid} token {at}: one chip "
             f"{one[rid][at]}, mesh {sharded[rid][at]}; max |logit "
             f"difference| of a prefill over the common prefix: {gap:.4e}")
    print(f"(a) greedy streams identical on one chip and on the mesh "
          f"({sum(map(len, one))} tokens)")
    # the engines hold reference cycles: collect them, or (a)'s weights
    # and pools stay on the chips while (b) builds 8 GiB a chip
    del one_params, mesh_params
    gc.collect()
    jax.clear_caches()
    serve(full, True, "(b) mesh")


def logit_gap(cfg, one_params, mesh_params, plan, tokens) -> float:
    """Max |logit difference| between one chip and the mesh plan, for a
    prefill over ``tokens`` (the streams' common prefix)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.models import registry
    from repro.sharding import tp

    def last_logits(params, toks):
        return registry.prefill(params, cfg, toks[None])[0]

    toks = jnp.asarray(tokens)
    a = jax.jit(last_logits)(one_params, toks)
    b = tp.wrap(plan, last_logits, (tp.param_specs(mesh_params, plan), P()),
                P())(mesh_params, toks)
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = tpu_devices()
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch import compile_cache
    cache = Path(compile_cache.configure())
    held = sum(f.stat().st_size for f in cache.rglob("*") if f.is_file()) \
        if cache.is_dir() else 0
    print(f"jax {jax.__version__}; {len(devs)} x {devs[0].device_kind}; "
          f"compile cache: {cache} ({held / 2**20:.1f} MiB at start)")
    if args.four_chips:
        serve_four_chips(args.seed, devs)
    else:
        serve_one_chip(args.seed, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
