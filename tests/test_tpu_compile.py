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

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs
from repro.kernels import ops
from repro.models import registry
from repro.sharding import tp

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


# (arch, slots, max_seq, chips): the benchmark's two cells — qwen2-0.5b on
# one chip, qwen3-8b tensor parallel over a (1, 4) mesh (2 KV heads of 128
# a chip)
CELLS = {"qwen2-0.5b.chat": ("qwen2-0.5b", 32, 4096, 1),
         "qwen3-8b-tp4.batch": ("qwen3-8b", 16, 2048, 4)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_decode_step_has_one_paged_kernel(cell, topo, pallas):
    """The served decode step at each benchmark cell's shape compiles for
    v5e with exactly one paged attention kernel in its layer loop, found
    the way the benchmark's trace reader finds it (a ``tpu_custom_call``
    whose first operand is the flattened ``s32[slots * pages_per_seq]``
    table), and fits a chip's 16 GiB."""
    arch, slots, max_seq, chips = CELLS[cell]
    cfg = configs.get(arch)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    plan = tp.make_plan(cfg, mesh, slots) if chips > 1 else None
    params = jax.eval_shape(lambda k: registry.init(cfg, k)[0],
                            jax.random.PRNGKey(0))
    if chips > 1:                   # the four-chip cell serves bf16 weights
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, cfg.jnp_dtype), params)
    pool = jax.eval_shape(lambda: registry.init_paged_cache(
        cfg, slots * max_seq // PAGE + 1, PAGE)[0])
    pspecs = tp.param_specs(params, plan) if plan else \
        jax.tree.map(lambda _: P(), params)
    kv = tp.kv_specs(plan) if plan else {"k": P(), "v": P()}

    def place(tree, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
    table = jax.ShapeDtypeStruct((slots, max_seq // PAGE), jnp.int32)
    rep = (P(),) * 3
    args = (place(params, pspecs), place(pool, kv)) + tuple(
        place(x, spec) for x, spec in zip((table, i32, i32), rep))

    def step(params, pool, table, token, pos):
        return registry.decode_step_paged(params, cfg, pool, table, token,
                                          pos)

    if plan:
        fn = tp.wrap(plan, step, (pspecs, kv) + rep, (P(), kv), (1,))
    else:
        fn = jax.jit(step, donate_argnums=(1,))
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    table_len = slots * max_seq // PAGE
    kernels = re.findall(r'custom_call_target="tpu_custom_call", '
                         r'operand_layout_constraints=\{s32\[(\d+)\]',
                         text)
    assert kernels == [str(table_len)], kernels
    (body,) = re.findall(r"\n(%[\w.]+) \([^\n]*\n(?:  [^\n]*\n)*?"
                         r"[^\n]*operand_layout_constraints=\{s32\["
                         + str(table_len) + r"\]", text)
    assert re.search(r"while\([^\n]*body=" + re.escape(body), text), \
        "the paged kernel is not in the layer loop's body"
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert used < 16 * 2**30
