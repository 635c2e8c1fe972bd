"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes.

Inputs and oracle outputs are generated once per (kernel, shape, dtype)
and shared across the variant axis through a module-scoped cache — the
interpret-mode Pallas run is the thing under test; regenerating identical
oracles per variant was pure overhead. The heaviest interpret-mode cases
carry ``@pytest.mark.slow`` (excluded from the tier-1 run, see pytest.ini).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (flash_decode as fd, fused_add_rmsnorm as rms,
                           merge_attn_states as mrg, ops, ref,
                           silu_and_mul as silu)

F32, BF16 = jnp.float32, jnp.bfloat16

def tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == BF16 \
        else dict(rtol=1e-5, atol=1e-4)


def allclose(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol(dtype))


@pytest.fixture(scope="module")
def case_cache():
    """Shared (kernel, shape, dtype) -> (inputs, oracle) memo."""
    return {}


def _memo(cache, key, build):
    if key not in cache:
        cache[key] = build()
    return cache[key]


SILU_SHAPES = [(1, 128), (16, 4096), (33, 5120), (7, 256), (128, 11008)]


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", SILU_SHAPES)
@pytest.mark.parametrize("variant", [silu.BASELINE, silu.OPTIMIZED,
                                     silu.SiluMulVariant(block_rows=8,
                                                         fast_exp=True)])
def test_silu_and_mul(shape, dtype, variant, case_cache):
    def build():
        x = jax.random.normal(jax.random.PRNGKey(0),
                              (shape[0], 2 * shape[1]), dtype) * 3
        return x, ref.silu_and_mul(x)
    x, want = _memo(case_cache, ("silu", shape, str(dtype)), build)
    got = silu.silu_and_mul(x, variant, interpret=True)
    allclose(got, want, dtype)


RMS_SHAPES = [(1, 128), (256, 4096), (33, 5120), (512, 14336)]


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", RMS_SHAPES)
@pytest.mark.parametrize("variant", [rms.BASELINE, rms.OPTIMIZED])
def test_fused_add_rmsnorm(shape, dtype, variant, case_cache):
    def build():
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(ks[0], shape, dtype)
        r = jax.random.normal(ks[1], shape, dtype)
        w = (1 + 0.1 * jax.random.normal(ks[2], (shape[1],))).astype(dtype)
        return (x, r, w), ref.fused_add_rmsnorm(x, r, w)
    (x, r, w), (wy, wr) = _memo(case_cache, ("rms", shape, str(dtype)), build)
    y, ro = rms.fused_add_rmsnorm(x, r, w, variant=variant, interpret=True)
    allclose(y, wy, dtype)
    allclose(ro, wr, dtype)


MERGE_SHAPES = [(17, 1, 128), (512, 32, 256), (100, 7, 128),
                pytest.param((512, 64, 128), marks=pytest.mark.slow)]


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", MERGE_SHAPES)
@pytest.mark.parametrize("variant", [mrg.BASELINE, mrg.OPTIMIZED,
                                     mrg.MergeVariant(fuse_s_out=False)])
def test_merge_attn_states(shape, dtype, variant, case_cache):
    s, h, d = shape

    def build():
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        va = jax.random.normal(ks[0], (s, h, d), dtype)
        vb = jax.random.normal(ks[1], (s, h, d), dtype)
        sa = jax.random.normal(ks[2], (s, h)) * 8
        sb = jax.random.normal(ks[3], (s, h)) * 8
        sb = jnp.where(jax.random.uniform(ks[4], (s, h)) < 0.1, -jnp.inf, sb)
        return (va, sa, vb, sb), ref.merge_attn_states_lse(va, sa, vb, sb)
    (va, sa, vb, sb), (wv, ws) = _memo(case_cache,
                                       ("merge", shape, str(dtype)), build)
    vo, so = mrg.merge_attn_states_lse(va, sa, vb, sb, variant,
                                       interpret=True)
    allclose(vo, wv, dtype)
    np.testing.assert_allclose(np.asarray(so), np.asarray(ws),
                               rtol=1e-5, atol=1e-5)


FLASH_SHAPES = [  # (b, hq, hkv, dh, s)
    (1, 8, 8, 64, 257),
    pytest.param((3, 14, 2, 128, 1000), marks=pytest.mark.slow),
    pytest.param((2, 16, 4, 64, 2048), marks=pytest.mark.slow)]


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("variant", [fd.BASELINE, fd.OPTIMIZED])
def test_flash_decode(shape, dtype, variant, case_cache):
    b, hq, hkv, dh, s = shape

    def build():
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (b, hq, dh), dtype)
        k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
        v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
        kv_len = jax.random.randint(ks[3], (b,), 1, s + 1)
        return ((q, k, v, kv_len),
                ref.flash_decode_attention(q, k, v, kv_len=kv_len))
    (q, k, v, kv_len), want = _memo(case_cache,
                                    ("flash", shape, str(dtype)), build)
    got = fd.flash_decode_attention(q, k, v, kv_len=kv_len, variant=variant,
                                    interpret=True)
    allclose(got, want, dtype)


PAGED_FLASH_SHAPES = [  # (b, hq, hkv, dh, s)
    (2, 8, 2, 64, 256),
    pytest.param((3, 12, 4, 64, 512), marks=pytest.mark.slow)]


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", PAGED_FLASH_SHAPES)
@pytest.mark.parametrize("variant", [fd.PAGED_BASELINE, fd.PAGED_OPTIMIZED,
                                     fd.PagedFlashDecodeVariant(
                                         page_size=32, mask_oob=True)])
def test_paged_flash_decode(shape, dtype, variant, case_cache):
    """The paged kernel gathers K/V through a shuffled page table yet must
    reproduce contiguous decode attention (the space's oracle)."""
    b, hq, hkv, dh, s = shape

    def build():
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        q = jax.random.normal(ks[0], (b, hq, dh), dtype)
        k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
        v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
        kv_len = jax.random.randint(ks[3], (b,), 1, s + 1)
        return ((q, k, v, kv_len),
                ref.flash_decode_attention(q, k, v, kv_len=kv_len))
    (q, k, v, kv_len), want = _memo(case_cache,
                                    ("paged_flash", shape, str(dtype)), build)
    got = fd._paged_run(variant, q, k, v, kv_len, interpret=True)
    allclose(got, want, dtype)


WALK_SHAPES = {"qwen2": (14, 2, 64), "qwen3": (8, 2, 128)}  # hq, hkv, dh
WALK_PAGE, WALK_PAGES = 16, 16                  # a 256-row table


@pytest.mark.parametrize("dtype,ppb", [(F32, 1), (F32, 3), (F32, None),
                                       (F32, WALK_PAGES), (BF16, None)])
@pytest.mark.parametrize("shape", sorted(WALK_SHAPES))
def test_paged_walk_lengths(shape, dtype, ppb):
    """The slot-grid kernel walks each slot's live blocks only: over a
    shuffled table, lengths 0, 1, a page -1, a page, a block boundary
    +-1, the full table and past it (clamped) all match the reference,
    for one page a block, blocks that do not divide the table, the shape
    rule (capped at the table) and the whole table a block; a length of
    0 gives zeros."""
    hq, hkv, dh = WALK_SHAPES[shape]
    seq = WALK_PAGE * WALK_PAGES
    rows = WALK_PAGE * fd.block_pages(
        ppb, WALK_PAGE, WALK_PAGE * hkv * dh * jnp.dtype(dtype).itemsize,
        WALK_PAGES)
    lens = sorted({0, 1, WALK_PAGE - 1, WALK_PAGE, rows - 1, rows,
                   min(rows + 1, seq), seq, seq + 40})
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    b = len(lens)
    q = jax.random.normal(ks[0], (b, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, seq, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, seq, hkv, dh), dtype)
    kv_len = jnp.asarray(lens, jnp.int32)
    k_pages, v_pages, table = fd._page_kv(k, v, WALK_PAGE)
    variant = dataclasses.replace(fd.PAGED_OPTIMIZED, pages_per_block=ppb)
    got = fd.paged_flash_decode_attention(q, k_pages, v_pages, table,
                                          kv_len=kv_len, variant=variant,
                                          interpret=True)
    want = ref.paged_flash_decode_attention(
        q, k_pages, v_pages, table, kv_len=jnp.minimum(kv_len, seq))
    allclose(got, want, dtype)
    assert not np.asarray(got[0], np.float32).any()


def test_paged_ref_gather_is_bitwise_contiguous():
    """ops CPU dispatch: gathering pages through the table then attending
    must be BITWISE equal to contiguous attention — the serving engine's
    stream equivalence rests on this."""
    b, hq, hkv, dh, s = 2, 8, 2, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, hq, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    kv_len = jnp.array([100, 37])
    k_pages, v_pages, table = fd._page_kv(k, v, 16)
    got = ops.paged_flash_decode_attention(q, k_pages, v_pages, table,
                                           kv_len=kv_len)
    want = ref.flash_decode_attention(q, k, v, kv_len=kv_len)
    assert bool(jnp.all(got == want))


def test_split_kv_merge_identity():
    """Distributed split-KV invariant: merging per-shard partial states with
    Kernel 1 equals attention over the whole cache."""
    b, hq, hkv, dh, s = 2, 8, 2, 64, 512
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (b, hq, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    kv_len = jnp.array([400, 150])
    want = ref.flash_decode_attention(q, k, v, kv_len=kv_len)
    half = s // 2
    l1 = jnp.minimum(kv_len, half)
    l2 = jnp.maximum(kv_len - half, 0)
    o1 = ref.flash_decode_attention(q, k[:, :half], v[:, :half], kv_len=l1)
    s1 = ref.flash_decode_lse(q, k[:, :half], kv_len=l1)
    o2 = ref.flash_decode_attention(q, k[:, half:], v[:, half:], kv_len=l2)
    s2 = ref.flash_decode_lse(q, k[:, half:], kv_len=l2)
    o2 = jnp.where(jnp.isneginf(s2)[..., None], 0.0, o2)
    om, sm = ref.merge_attn_states_lse(o1, s1, o2, s2)
    np.testing.assert_allclose(np.asarray(om), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ops_dispatch_and_reintegration():
    """ops.* dispatches to ref on CPU; set_variants installs tuned kernels
    (the paper's post-processing)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 512))
    np.testing.assert_allclose(np.asarray(ops.silu_and_mul(x)),
                               np.asarray(ref.silu_and_mul(x)), rtol=1e-6)
    old = ops.get_variant("silu_and_mul")
    try:
        tuned = silu.SiluMulVariant(name="tuned", block_rows=64)
        ops.set_variants(silu_and_mul=tuned)
        assert ops.get_variant("silu_and_mul").name == "tuned"
        y = ops.silu_and_mul(x, impl="pallas")
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ref.silu_and_mul(x)),
                                   rtol=1e-5, atol=1e-5)
    finally:
        ops.set_variants(silu_and_mul=old)
    with pytest.raises(KeyError):
        ops.set_variants(nonexistent_kernel=None)
