"""Sharding rules, HLO parser, roofline arithmetic, serving engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.roofline import analysis
from repro.roofline.hlo_parser import Module, _shape_elems_bytes
from repro.sharding import rules


@pytest.fixture(scope="module")
def mesh16():
    # 1 real device: build an abstract 4x4 mesh over fake ids is not
    # possible; use a 1x1 mesh for API checks and a fake-device mesh for
    # rule checks via mesh shape introspection only.
    return make_mesh((1, 1), ("data", "model"))


def test_spec_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    # with axis size 1 everything divides; exercise the structure
    s = rules.spec_for(("embed", "heads", "head_dim"), (896, 14, 64), mesh)
    assert isinstance(s, P)


class _FakeMesh:
    """Shape-only mesh stand-in for rule arithmetic."""
    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as np
        self.devices = np.zeros(shape)
        self.shape = dict(zip(names, shape))


def test_rules_respect_divisibility():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    # 14 heads don't divide 16 -> replicated; 64000 vocab does -> sharded
    assert rules._resolve("heads", 14, mesh) is None
    assert rules._resolve("heads", 32, mesh) == "model"
    assert rules._resolve("vocab", 64000, mesh) == "model"
    assert rules._resolve("embed", 896, mesh) == "data"
    assert rules._resolve("embed_vocab", 152064, mesh) is None


def test_rules_multipod_batch():
    mesh = _FakeMesh((2, 16, 16), ("pod", "data", "model"))
    assert rules._resolve("batch", 256, mesh) == ("pod", "data")
    assert rules._resolve("batch", 16, mesh) == "data"  # 16 % 32 != 0


# Real-mesh rule checks need >1 device, and the in-process jax backend is
# already initialized single-CPU — so they run in a subprocess that sets
# --xla_force_host_platform_device_count before importing jax.
_MESH_RULES_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch.mesh import make_mesh
from repro.sharding import batch_spec, rules, sharding_for, spec_for, tp

m22 = make_mesh((2, 2), ("data", "model"))
m14 = make_mesh((1, 4), ("data", "model"))
pod = make_mesh((2, 2, 1), ("pod", "data", "model"))

# logical -> physical resolution on a real mesh
qwen2 = configs.get("qwen2-0.5b")     # 14 heads, 2 kv heads, d_ff 4864
yi = configs.get("yi-34b")            # 56 heads, 8 kv heads
assert qwen2.n_heads == 14 and yi.n_heads == 56

# non-divisible-axis replication fallback: qwen2's 14 heads on a 4-way
# model axis replicate; yi's 56 shard; both shard on a 2-way axis
assert rules._resolve("heads", qwen2.n_heads, m14) is None
assert rules._resolve("heads", qwen2.n_heads, m22) == "model"
assert rules._resolve("heads", yi.n_heads, m14) == "model"
assert rules._resolve("kv_heads", yi.n_kv_heads, m14) == "model"
# the fallback keeps MLP/vocab sharded
assert rules._resolve("mlp", qwen2.d_ff, m14) == "model"
assert rules._resolve("vocab", qwen2.padded_vocab, m14) == "model"

# spec_for: per-axis resolution with one-mesh-axis-at-most-once dedup
assert spec_for(("embed", "heads", "head_dim"),
                (qwen2.d_model, 14, 64), m14) == P("data", None, None)
assert spec_for(("embed", "heads", "head_dim"),
                (yi.d_model, 56, 128), m14) == P("data", "model", None)

# pod+data composition on the multi-pod mesh (pod*data = 4 here)
assert rules._resolve("batch", 8, pod) == ("pod", "data")
assert rules._resolve("batch", 2, pod) == "data"     # 2 % 4 != 0
assert batch_spec(pod, None) == P(("pod", "data"), None)
assert batch_spec(m22, None) == P("data", None)

# sharding_for round-trips through a real device_put
x = jax.numpy.zeros((yi.d_model, 56, 128))
s = sharding_for(("embed", "heads", "head_dim"), x.shape, m14)
assert isinstance(s, NamedSharding)
xs = jax.device_put(x, s)
assert xs.sharding.spec == P("data", "model", None)

# the serving plan resolves through the same rules
plan = tp.make_plan(configs.smoke("qwen3-8b"), m22, slots=4)
assert plan.describe() == {"data": 2, "model": 2, "heads_tp": True,
                           "mlp_tp": True, "vocab_tp": True,
                           "batch_dp": True}
plan = tp.make_plan(configs.smoke("qwen2-0.5b"), m14, slots=4)
assert plan.describe() == {"data": 1, "model": 4, "heads_tp": False,
                           "mlp_tp": True, "vocab_tp": True,
                           "batch_dp": False}
print("MESH_RULES_OK")
"""


def test_rules_on_real_forced_host_mesh():
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_RULES_SCRIPT],
                          env=env, cwd=repo, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "MESH_RULES_OK" in proc.stdout


def test_tree_shardings_structure(mesh16):
    params = {"a": jnp.zeros((8, 4)), "b": {"c": jnp.zeros((4,))}}
    axes = {"a": ("embed", "mlp"), "b": {"c": ("embed",)}}
    sh = rules.tree_shardings(params, axes, mesh16)
    assert jax.tree.structure(sh) == jax.tree.structure(params)


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

_TOY = """
HloModule toy

%body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %ag = f32[128,256]{1,0} all-gather(%gte), replica_groups={}
  %dot.1 = f32[128,128]{1,0} dot(%ag, %ag), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  ROOT %t = (s32[], f32[128,256]) tuple(%c, %ag)
}

%cond (p: (s32[], f32[128,256])) -> pred[] {
  %p = (s32[], f32[128,256]) parameter(0)
  ROOT %cmp = pred[] compare(%gte, %k), direction=LT
}

ENTRY %main (x: f32[128,256]) -> f32[128,256] {
  %x = f32[128,256]{1,0} parameter(0)
  %w = (s32[], f32[128,256]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
  %ar = f32[128,256]{1,0} all-reduce(%x), to_apply=%sum
  ROOT %out = f32[128,256]{1,0} copy(%x)
}
"""


def test_hlo_parser_trip_counts_and_collectives():
    m = Module(_TOY)
    c = m.entry_cost()
    ag_bytes = 128 * 256 * 4
    ar_bytes = 128 * 256 * 4 * 2.0          # ring all-reduce factor
    assert c["coll"]["all-gather"] == pytest.approx(12 * ag_bytes)
    assert c["coll"]["all-reduce"] == pytest.approx(ar_bytes)
    # dot: 2 * 128*128 out * 256 contract, counted x12 trips
    assert c["flops"] == pytest.approx(12 * 2 * 128 * 128 * 256, rel=0.01)


def test_shape_parsing():
    assert _shape_elems_bytes("f32[128,256]{1,0}") == (128 * 256,
                                                       128 * 256 * 4)
    e, b = _shape_elems_bytes("(bf16[8,4], pred[16])")
    assert e == 32 + 16 and b == 64 + 16


def test_flash_scope_traffic_is_skipped():
    hlo = """
ENTRY %main (x: f32[1024,1024]) -> f32[1024,1024] {
  %x = f32[1024,1024]{1,0} parameter(0)
  %big = f32[1024,1024]{1,0} copy(%x), metadata={op_name="jit(f)/flash_kernel/softmax"}
  ROOT %o = f32[1024,1024]{1,0} copy(%big)
}
"""
    c = Module(hlo).entry_cost()
    assert c["traffic"] == pytest.approx(2 * 1024 * 1024 * 4)  # ROOT only


# ---------------------------------------------------------------------------
# roofline arithmetic
# ---------------------------------------------------------------------------

def test_roofline_terms_and_dominance():
    r = analysis.Roofline(
        arch="x", shape="train_4k", mesh="16x16", chips=256,
        flops_per_chip=197e12 * 0.010,          # 10 ms of compute
        bytes_per_chip=819e9 * 0.002,           # 2 ms of HBM
        coll_bytes_per_chip=50e9 * 0.020,       # 20 ms of ICI
        coll_breakdown={}, model_flops_global=197e12 * 0.010 * 256 * 0.5,
        peak_memory_per_chip=8 * 2**30)
    assert r.compute_s == pytest.approx(0.010)
    assert r.memory_s == pytest.approx(0.002)
    assert r.collective_s == pytest.approx(0.020)
    assert r.dominant == "collective"
    assert r.step_time_s == pytest.approx(0.020)
    assert r.useful_ratio == pytest.approx(0.5)
    assert r.mfu == pytest.approx(0.010 * 0.5 / 0.020)


def test_model_flops_accounting():
    cfg = [c for c in [__import__("repro.configs", fromlist=["get"])
           .get("olmoe-1b-7b")]][0]
    n = cfg.activated_params
    assert analysis.model_flops(cfg, "train", 1000) == 6.0 * n * 1000
    assert analysis.model_flops(cfg, "decode", 128) == 2.0 * n * 128
    # MoE activated params exclude inactive experts
    dense_equiv = cfg.n_layers * 3 * cfg.d_model * cfg.expert_ff \
        * cfg.n_experts
    active = cfg.n_layers * 3 * cfg.d_model * cfg.expert_ff * cfg.top_k
    assert n < dense_equiv
    assert n > active


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_engine_continuous_batching():
    from repro.serving.engine import Engine, Request
    cfg = __import__("repro.configs", fromlist=["smoke"]).smoke("qwen2-0.5b")
    from repro.models import registry
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    for rid in range(4):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, (8,),
                                               dtype=np.int32),
                           max_new_tokens=4))
    done = eng.run(max_steps=200)
    assert len(done) == 4
    for r in done:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < cfg.padded_vocab for t in r.out_tokens)
