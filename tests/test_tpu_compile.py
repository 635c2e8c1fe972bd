"""Ahead-of-time compiles for a described TPU v5e chip, at qwen2-0.5b's
published widths: the main-path Pallas kernels and the full paged decode
step. Nothing runs — the TPU compiler installed with JAX compiles for a
chip that is described, not attached — so these catch what interpret
mode never checks (block tiling, fast-memory use, programs too large for
the device) at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file. Keep these tests in this one file, so that one
worker loads it."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.models import registry

CFG = configs.get("qwen2-0.5b")
SLOTS, MAX_SEQ, PAGE = 8, 2048, 16
N_PAGES = SLOTS * MAX_SEQ // PAGE + 1            # + the engine's trap page


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    """The described chip. The TPU library writes its logs under
    ``TPU_LOG_DIR`` (a fixed, shared directory when unset), so point it
    at this session's temporary directory while the module runs."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """Steer ``ops`` to the compiled Pallas kernels (``auto`` would pick
    the jnp references: the process's backend is the CPU), with JAX's
    persistent compilation cache off — a compile for a described chip is
    written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_use_pallas",
                        lambda impl: (impl != "ref", False))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "compiled without a Pallas kernel"
    return compiled


def _kernel_case(name):
    """(op, argument shapes) at the served decode shapes."""
    bf, f32, i32 = CFG.jnp_dtype, jnp.float32, jnp.int32
    hq, hkv, dh, d = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim, CFG.d_model
    S = jax.ShapeDtypeStruct
    if name == "paged_flash_decode":
        kv = S((N_PAGES, PAGE, hkv, dh), bf)
        return (lambda q, k, v, table, n: ops.paged_flash_decode_attention(
                    q, k, v, table, kv_len=n),
                (S((SLOTS, hq, dh), bf), kv, kv,
                 S((SLOTS, MAX_SEQ // PAGE), i32), S((SLOTS,), i32)))
    if name == "flash_decode":
        kv = S((SLOTS, MAX_SEQ, hkv, dh), bf)
        return (lambda q, k, v, n: ops.flash_decode_attention(
                    q, k, v, kv_len=n),
                (S((SLOTS, hq, dh), bf), kv, kv, S((SLOTS,), i32)))
    if name == "fused_add_rmsnorm":
        x = S((SLOTS, 1, d), bf)
        return ops.fused_add_rmsnorm, (x, x, S((d,), f32))
    if name == "silu_and_mul":
        return ops.silu_and_mul, (S((SLOTS, 1, 2 * CFG.d_ff), bf),)
    if name == "merge_attn_states_lse":
        v, s = S((SLOTS, hq, dh), f32), S((SLOTS, hq), f32)
        return ops.merge_attn_states_lse, (v, s, v, s)
    raise KeyError(name)


@pytest.mark.parametrize("name", ["paged_flash_decode", "flash_decode",
                                  "fused_add_rmsnorm", "silu_and_mul",
                                  "merge_attn_states_lse"])
def test_kernel_compiles_for_v5e(name, one_chip, pallas):
    op, shapes = _kernel_case(name)
    _compile(op, *[jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                   for s in shapes])


def test_full_width_paged_decode_step_compiles_for_v5e(one_chip, pallas):
    """The serving engine's decode hot path (24 layers of paged attention,
    fused add-RMSNorm and SwiGLU) at qwen2-0.5b's widths, float32 master
    weights as the engine holds them, fits one chip."""
    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda k: registry.init(CFG, k)[0], jax.random.PRNGKey(0)))
    pool = place(jax.eval_shape(
        lambda: registry.init_paged_cache(CFG, N_PAGES, PAGE)[0]))
    i32 = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((SLOTS, MAX_SEQ // PAGE), jnp.int32,
                                 sharding=one_chip)

    def step(params, pool, table, token, pos):
        return registry.decode_step_paged(params, CFG, pool, table, token,
                                          pos)

    compiled = _compile(step, params, pool, table, i32, i32)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert used < 16 * 2**30
