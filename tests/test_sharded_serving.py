"""Tensor-parallel serving: bit-identity vs the single-device engine,
and the paged pool built in its mesh sharding.

Each mesh test runs in a subprocess (``tools/sharded_check.py``, or a
script of its own) so the forced-host device count
(``--xla_force_host_platform_device_count``) lands in XLA_FLAGS *before*
jax initializes — the in-process test session has already created the
default single-CPU backend. The harness runs both
engines in one subprocess and compares token streams plus every
deterministic counter (steps, readbacks, preemptions, prefix hits, CoW
copies, recoveries) across scenarios: greedy, seeded sampling, forced
swap preemption, radix prefix-cache hits, and chaos device-fault
recovery.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK = os.path.join(REPO, "tools", "sharded_check.py")


def _run_check(arch, mesh, devices=4):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)  # the harness sets the device count itself
    proc = subprocess.run(
        [sys.executable, CHECK, "--arch", arch, "--mesh", mesh,
         "--devices", str(devices), "--json"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, \
        f"sharded check failed:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(proc.stdout)


def _assert_scenarios(report):
    sc = report["scenarios"]
    assert set(sc) == {"greedy", "sampling", "preempt", "prefix", "chaos"}
    for name, r in sc.items():
        assert r["ok"], f"{name}: {r['notes']}"
        assert r["streams_match"], name
        # one batched host readback per dispatched step, exactly
        assert r["counters"]["readbacks"] == r["counters"]["steps"]
    assert sc["preempt"]["counters"]["preemptions"] > 0
    assert sc["prefix"]["counters"]["prefix_hit_tokens"] > 0
    assert sc["chaos"]["counters"]["recoveries"] == 1


def test_sharded_streams_bit_identical_full_tp():
    """qwen3-8b smoke on a (2, 2) mesh: heads, MLP, and vocab all shard
    over ``model``; the slot batch shards over ``data``."""
    report = _run_check("qwen3-8b", "2,2")
    assert report["ok"], report
    assert report["plan"] == {"data": 2, "model": 2, "heads_tp": True,
                              "mlp_tp": True, "vocab_tp": True,
                              "batch_dp": True}
    _assert_scenarios(report)


def test_sharded_streams_bit_identical_replicated_heads_fallback():
    """qwen2-0.5b smoke on a (1, 4) mesh: 1 KV head can't shard over 4,
    so heads replicate while the MLP and vocab axes still shard — the
    fallback ``sharding/rules.py`` documents."""
    report = _run_check("qwen2-0.5b", "1,4")
    assert report["ok"], report
    assert report["plan"] == {"data": 1, "model": 4, "heads_tp": False,
                              "mlp_tp": True, "vocab_tp": True,
                              "batch_dp": False}
    _assert_scenarios(report)


POOL_CHECK = r"""
import json, re, sys
sys.path.insert(0, {src!r})
from repro.launch.mesh import force_host_devices, make_mesh
force_host_devices(4)
import jax
import numpy as np
from jax.sharding import NamedSharding
from repro import configs
from repro.models import registry
from repro.reliability import Fault
from repro.serving import ChaosInjector, LLMEngine
from repro.sharding import tp

cfg = configs.smoke("qwen3-8b")
params, _ = registry.init(cfg, jax.random.PRNGKey(0))
mesh = make_mesh((2, 2), ("data", "model"))
kw = dict(slots=4, max_seq=128)
pool_shape = registry.paged_cache_spec(cfg, 4 * 128 // 16 + 1,
                                      16)[0]["k"].shape

# every array of the whole pool's shape that is made concrete, or placed,
# on one device, while the engines are built and serve
whole = []
def spy(fn):
    def wrapped(*a, **k):
        for x in jax.tree.leaves((a, k)):
            if getattr(x, "shape", None) == pool_shape \
                    and not isinstance(x, jax.core.Tracer):
                whole.append(f"{{fn.__name__}} input")
        out = fn(*a, **k)
        for x in jax.tree.leaves(out):
            if isinstance(x, jax.Array) \
                    and not isinstance(x, jax.core.Tracer) \
                    and x.shape == pool_shape \
                    and len(x.sharding.device_set) == 1:
                whole.append(f"{{fn.__name__}} output")
        return out
    return wrapped
jax.device_put = spy(jax.device_put)
jax.numpy.zeros = spy(jax.numpy.zeros)

def placement(eng):
    want = NamedSharding(mesh, tp.kv_spec(eng._plan))
    leaves = jax.tree.leaves(eng.cache)
    return {{
        "shape_ok": all(x.shape == pool_shape for x in leaves),
        "sharding_ok": all(x.sharding == want for x in leaves),
        "shard_heads": sorted({{s.data.shape[3] for x in leaves
                               for s in x.addressable_shards}}),
        "devices": sorted({{len(x.sharding.device_set) for x in leaves}}),
        "bytes_per_chip": eng.stats()["kv_pool_bytes_per_chip"],
        "live_whole": sum(1 for x in jax.live_arrays()
                          if x.shape == pool_shape
                          and len(x.sharding.device_set) == 1)}}

rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab, (int(n),), dtype=np.int32)
           for n in rng.integers(4, 17, 6)]
chaos = ChaosInjector([Fault(kind="device_fault", step=7, slot=1)])
llm = LLMEngine(params, cfg, mesh=mesh, chaos=chaos, **kw)
built = placement(llm.engine)
step = llm.engine._step_fn.lower(*llm.engine._step_args())
scopes = sorted(set(re.findall(r"tp\.gather_[a-z]+",
                               step.as_text(debug_info=True))))
outs = llm.generate(prompts, max_new_tokens=8)
recovered = placement(llm.engine)
streams = [list(map(int, o.tokens)) for o in outs]
on_mesh = list(whole)       # the one-device engine below builds it whole
plain = LLMEngine(params, cfg, chaos=ChaosInjector(
    [Fault(kind="device_fault", step=7, slot=1)]), **kw)
ref = [list(map(int, o.tokens)) for o in plain.generate(prompts,
                                                        max_new_tokens=8)]
print(json.dumps({{"built": built, "recovered": recovered,
                  "recoveries": llm.stats()["recoveries"],
                  "scopes": scopes,
                  "step_programs": llm.engine._step_fn._cache_size(),
                  "whole": on_mesh, "streams_match": streams == ref,
                  "one_device_bytes": plain.stats()["kv_pool_bytes_per_chip"],
                  "kv_heads": cfg.n_kv_heads, "model": 2}}))
"""


def test_the_pool_is_built_in_its_sharding_and_rebuilt_so_after_a_fault():
    """qwen3-8b smoke on a (2, 2) mesh: the paged pool is made straight
    into its heads-over-``model`` sharding, at start-up and again after a
    device fault, and no array of the whole pool's shape is ever made or
    placed on one device; greedy streams still match one device's."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    code = POOL_CHECK.format(src=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["recoveries"] == 1
    assert r["whole"] == []
    # the pool is built with the shardings the donated step returns: one
    # decode program, before and after the rebuilt pool
    assert r["step_programs"] == 1
    for when in ("built", "recovered"):
        p = r[when]
        assert p["shape_ok"] and p["sharding_ok"], (when, p)
        assert p["shard_heads"] == [r["kv_heads"] // r["model"]], (when, p)
        assert p["devices"] == [4] and p["live_whole"] == 0, (when, p)
        # one shard's bytes: the one-device pool over the model axis
        assert p["bytes_per_chip"] * r["model"] == r["one_device_bytes"]
    assert r["streams_match"]
    # each collective hook is findable by its named scope
    assert r["scopes"] == ["tp.gather_data", "tp.gather_heads",
                           "tp.gather_mlp", "tp.gather_vocab"]


def test_the_collective_hooks_are_the_identity_off_a_mesh():
    """With no plan active (one device) each hook returns its input and
    traces to no operation, named scope or not."""
    import jax
    import jax.numpy as jnp

    from repro.sharding import tp
    x = jnp.ones((2, 3, 4, 8))
    for hook in (tp.gather_heads, tp.gather_mlp, tp.gather_vocab,
                 tp.gather_data):
        assert hook(x) is x
        assert not jax.make_jaxpr(hook)(x).jaxpr.eqns
