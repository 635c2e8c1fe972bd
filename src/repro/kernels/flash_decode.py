"""Split-KV (FlashDecoding-style) GQA decode attention — Pallas TPU kernel.

This is the production consumer of paper Kernel 1: the KV cache is split
into sequence chunks; each chunk produces a partial attention state
``(V_partial, LSE)``; partials are merged with the ``merge_attn_states_lse``
math (running online-softmax merge in VMEM scratch across grid steps).

The same merge runs at TWO levels:
  1. on-chip: across KV chunks inside this kernel (this file), and
  2. cross-device: sequence-parallel decode shards the KV cache along the
     sequence axis and merges per-shard partials with collectives — the
     distributed form of Kernel 1 (``sharding/rules.py`` maps ``kv_seq``
     to the ``model`` axis for it). The paged serving engine does NOT use
     this path: its tensor-parallel plan (``repro.sharding.tp``) shards
     heads instead, because the cross-device LSE merge is not bitwise
     identical to single-device execution while head-sharded all-gathers
     are.

Grid: ``(batch * kv_heads, num_chunks)`` with the chunk axis sequential
("arbitrary"), carrying ``(acc, m, l)`` in VMEM scratch — the classic
online-softmax carry. Block shapes: q ``[group_pad, head_dim]``, k/v
``[chunk, head_dim]``.

Variant knobs (the space Astra searches):
  * ``chunk``        — KV rows per grid step (VMEM working set).
  * ``use_reciprocal`` — final normalize via rcp+mul vs divide.
  * ``mask_oob``     — predicate chunks entirely past ``kv_len`` (skip work)
    vs masking every score (baseline reads + masks everything).

**Paged form** (``paged_flash_decode_attention``): the production layout of
this kernel in a paged-KV serving engine. K/V live in a global page pool
``[num_pages, page_size, kv_heads, head_dim]`` shared by all requests; a
per-request page table maps logical page ``j`` to its physical page.  The
grid's sequential axis walks *logical* pages and the K/V BlockSpec
index_maps read the scalar-prefetched page table to DMA the right physical
block — the same online-softmax carry, with the gather folded into the
block fetch.  ``page_size`` is a search knob of its own registered space
(``paged_flash_decode``): it sets both the pool granule the serving engine
allocates in and this kernel's per-step working set.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels._common import round_up
from repro.kernels.registry import (KernelSpace, Knob, TestCase,
                                    register_kernel_space)

NEG_INF = -1e30  # finite -inf stand-in: keeps exp() well-defined on padding


@dataclasses.dataclass(frozen=True)
class FlashDecodeVariant:
    name: str = "baseline"
    chunk: int = 512
    use_reciprocal: bool = False
    mask_oob: bool = False

    def describe(self) -> str:
        return (f"{self.name}: chunk={self.chunk} rcp={self.use_reciprocal} "
                f"mask_oob={self.mask_oob}")


BASELINE = FlashDecodeVariant()
OPTIMIZED = FlashDecodeVariant(name="astra_opt", chunk=1024,
                               use_reciprocal=True, mask_oob=True)


def _init_carry(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _online_softmax_step(q, k, v, acc_ref, m_ref, l_ref, *,
                         pos0, kv_len, sm_scale):
    """One chunk of the running online-softmax merge. ``pos0`` is the
    absolute KV position of this chunk's first row (rows >= kv_len are
    masked)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # [G, C]
    # mask positions >= kv_len within this chunk
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)

    m_prev = m_ref[...][:, :1]                    # [G, 1]
    l_prev = l_ref[...][:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)    # [G, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    # merge_attn_states_lse math: rescale old accumulator, add new chunk
    alpha = jnp.exp(m_prev - m_new)               # e^{S_a - m}
    p = jnp.exp(s - m_new)                        # [G, C]
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _finalize_output(o_ref, acc_ref, l_ref, *, use_reciprocal):
    l = l_ref[...][:, :1]
    if use_reciprocal:
        inv = jnp.where(l > 0, pl.reciprocal(l, approx=False), 0.0)
        o_ref[0] = (acc_ref[...] * inv).astype(o_ref.dtype)
    else:
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *,
            chunk, hkv, sm_scale, use_reciprocal, mask_oob):
    j = pl.program_id(1)
    n_chunks = pl.num_programs(1)
    kv_len = len_ref[pl.program_id(0) // hkv]     # scalar-prefetched [b]

    @pl.when(j == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    def _step():
        _online_softmax_step(
            q_ref[0].astype(jnp.float32),             # [G, D]
            k_ref[0].astype(jnp.float32),             # [C, D]
            v_ref[0].astype(jnp.float32),             # [C, D]
            acc_ref, m_ref, l_ref,
            pos0=j * chunk, kv_len=kv_len, sm_scale=sm_scale)

    if mask_oob:
        # Optimized: skip chunks entirely past kv_len (saves the matmul+exp).
        pl.when(j * chunk < kv_len)(_step)
    else:
        _step()

    @pl.when(j == n_chunks - 1)
    def _finalize():
        _finalize_output(o_ref, acc_ref, l_ref, use_reciprocal=use_reciprocal)


def flash_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           kv_len: jax.Array | None = None,
                           sm_scale: float | None = None,
                           variant: FlashDecodeVariant = OPTIMIZED,
                           interpret: bool = False,
                           return_lse: bool = False):
    """Single-token GQA decode attention over a (chunked) KV cache.

    Args:
      q: ``[batch, q_heads, head_dim]``.
      k, v: ``[batch, seq, kv_heads, head_dim]``.
      kv_len: ``[batch]`` int32 valid lengths (default: full cache).

    Returns:
      ``[batch, q_heads, head_dim]`` (and ``[batch, q_heads]`` LSE when
      ``return_lse`` — the partial state consumed by the distributed merge).
    """
    b, hq, dh = q.shape
    _, s, hkv, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (dh ** 0.5)
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)

    chunk = min(variant.chunk, s)
    s_pad = round_up(s, chunk)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    n_chunks = s_pad // chunk

    g_pad = round_up(group, 8)  # sublane-align the query group
    # [b, hkv, G, D] with padded group rows
    q4 = q.reshape(b, hkv, group, dh)
    if g_pad != group:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    q3 = q4.reshape(b * hkv, g_pad, dh)
    # [b*hkv, s_pad, dh]
    k3 = jnp.swapaxes(k, 1, 2).reshape(b * hkv, s_pad, dh)
    v3 = jnp.swapaxes(v, 1, 2).reshape(b * hkv, s_pad, dh)

    kern = functools.partial(
        _kernel, chunk=chunk, hkv=hkv, sm_scale=sm_scale,
        use_reciprocal=variant.use_reciprocal, mask_oob=variant.mask_oob)

    # kv_len rides in SMEM as a scalar-prefetch operand (as in the paged
    # kernel): a (1, 1) SMEM block over a VMEM-tiled array breaks Mosaic's
    # (8, 128) block rule, which interpret mode never checks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_chunks),
        in_specs=[
            pl.BlockSpec((1, g_pad, dh), lambda i, j, ln: (i, 0, 0)),
            pl.BlockSpec((1, chunk, dh), lambda i, j, ln: (i, j, 0)),
            pl.BlockSpec((1, chunk, dh), lambda i, j, ln: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g_pad, dh), lambda i, j, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, dh), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g_pad, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q3, k3, v3)

    out = out.reshape(b, hkv, g_pad, dh)[:, :, :group].reshape(b, hq, dh)
    if not return_lse:
        return out
    # LSE is recomputed cheaply host-side for the distributed merge path.
    lse = ref.flash_decode_lse(q, k[:, :s], kv_len=kv_len, sm_scale=sm_scale)
    return out, lse


def cost(variant: FlashDecodeVariant, *, batch: int, q_heads: int,
         kv_heads: int, head_dim: int, seq: int, dtype,
         mean_kv_len: float | None = None):
    """Analytic v5e cost of decode attention over a ``[b, s, hkv, d]`` cache."""
    from repro.core import costmodel as cm

    import jax.numpy as jnp
    item = jnp.dtype(dtype).itemsize
    group = q_heads // kv_heads
    g_pad = round_up(group, 8)
    chunk = min(variant.chunk, seq)
    s_pad = round_up(seq, chunk)
    n_chunks = s_pad // chunk
    ops = cm.OP

    # fraction of chunks actually touched when predication is on
    frac = 1.0
    if variant.mask_oob and mean_kv_len is not None:
        frac = min(1.0, (mean_kv_len / chunk + 1) / n_chunks)

    kv_bytes = 2 * batch * kv_heads * s_pad * head_dim * item * frac
    q_bytes = batch * q_heads * head_dim * item
    o_bytes = batch * kv_heads * g_pad * head_dim * item

    mxu = 2 * 2 * batch * kv_heads * g_pad * head_dim * s_pad * frac  # qk + pv
    # per-score VPU: mask cmp+sel, exp, running max/sum, rescale
    vpu = batch * kv_heads * g_pad * s_pad * frac * (
        ops["exp"] + 2 * ops["cmp"] + ops["max"] + 2 * ops["fma"])
    vpu += batch * kv_heads * g_pad * head_dim * n_chunks * frac * 2 * ops["fma"]
    vpu += batch * kv_heads * g_pad * head_dim * (
        (ops["rcp"] + ops["mul"]) if variant.use_reciprocal else ops["div"])

    c = cm.Cost(
        hbm_bytes=kv_bytes + q_bytes + o_bytes,
        vpu_ops=vpu,
        mxu_flops=mxu,
        mxu_dtype="bf16" if item == 2 else "fp32",
        grid_steps=batch * kv_heads * n_chunks,
        n_calls=1,
        vmem_bytes=(2 * chunk * head_dim * item          # k, v blocks
                    + 2 * g_pad * head_dim * 4           # q, acc
                    + 2 * g_pad * 128 * 4),              # m, l
        align_waste_bytes=kv_bytes * (s_pad / seq - 1.0)
        + (g_pad - group) / max(group, 1) * q_bytes,
    )
    c.validate()
    return c


reference = ref.flash_decode_attention


SUITE_SHAPES = ({"batch": 8, "q_heads": 32, "kv_heads": 8, "head_dim": 128,
                 "seq": 4096},
                {"batch": 32, "q_heads": 14, "kv_heads": 2, "head_dim": 64,
                 "seq": 2048},
                {"batch": 4, "q_heads": 16, "kv_heads": 16, "head_dim": 128,
                 "seq": 8192})


def make_inputs(shape: dict, *, dtype=jnp.float32, seed: int = 0) -> TestCase:
    b, hq, hkv = shape["batch"], shape["q_heads"], shape["kv_heads"]
    dh, s = shape["head_dim"], shape["seq"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, dh), dtype=dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype=dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype=dtype)
    kv_len = jax.random.randint(ks[3], (b,), 1, s + 1)
    info = dict(shape)
    info.update(dtype=dtype, mean_kv_len=float(jnp.mean(kv_len)))
    return TestCase(f"[{b},{hq}/{hkv},{dh},s{s}]", (q, k, v, kv_len), info)


def _run(variant, q, k, v, kv_len, *, interpret=True):
    return flash_decode_attention(q, k, v, kv_len=kv_len, variant=variant,
                                  interpret=interpret)


def _oracle(q, k, v, kv_len):
    return ref.flash_decode_attention(q, k, v, kv_len=kv_len)


@register_kernel_space
def _space() -> KernelSpace:
    return KernelSpace(
        name="flash_decode",
        baseline=BASELINE,
        default=OPTIMIZED,
        run=_run,
        oracle=_oracle,
        cost=cost,
        knobs=(
            Knob("mask_oob", "bool", attacks=("memory", "compute"),
                 target=True,
                 note="predicate chunks past kv_len (skip DMA + compute)"),
            Knob("chunk", "pow2", 128, 4096, attacks=("overhead",),
                 note="KV rows per grid step"),
            Knob("use_reciprocal", "bool", attacks=("compute",), target=True),
        ),
        suite_shapes=SUITE_SHAPES,
        make_inputs=make_inputs,
    )


# ==========================================================================
# Paged variant — K/V gathered through a page table (paged-KV serving form)
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class PagedFlashDecodeVariant:
    """Knobs of the paged kernel. ``page_size`` is the pool granule: the
    serving engine allocates KV in ``page_size``-row pages and this kernel
    processes one page per sequential grid step (the paged analogue of
    ``chunk``). At apply time the kernel reads the page size off the pool's
    shape; the knob steers the *search*, whose verdict sizes the pool."""
    name: str = "baseline"
    page_size: int = 16
    use_reciprocal: bool = False
    mask_oob: bool = False

    def describe(self) -> str:
        return (f"{self.name}: page_size={self.page_size} "
                f"rcp={self.use_reciprocal} mask_oob={self.mask_oob}")


PAGED_BASELINE = PagedFlashDecodeVariant()
PAGED_OPTIMIZED = PagedFlashDecodeVariant(name="astra_opt", page_size=64,
                                          use_reciprocal=True, mask_oob=True)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  page, hkv, sm_scale, use_reciprocal, mask_oob):
    i = pl.program_id(0)                  # batch * kv_head
    j = pl.program_id(1)                  # LOGICAL page index
    n_pages = pl.num_programs(1)
    kv_len = len_ref[i // hkv]

    @pl.when(j == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    def _step():
        _online_softmax_step(
            q_ref[0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),          # [page, D]
            v_ref[0, 0].astype(jnp.float32),
            acc_ref, m_ref, l_ref,
            pos0=j * page, kv_len=kv_len, sm_scale=sm_scale)

    if mask_oob:
        # skip logical pages entirely past kv_len (their physical blocks
        # may belong to other requests — never read, never computed)
        pl.when(j * page < kv_len)(_step)
    else:
        _step()

    @pl.when(j == n_pages - 1)
    def _finalize():
        _finalize_output(o_ref, acc_ref, l_ref, use_reciprocal=use_reciprocal)


def paged_flash_decode_attention(q: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array, page_table: jax.Array, *,
                                 kv_len: jax.Array | None = None,
                                 sm_scale: float | None = None,
                                 variant: PagedFlashDecodeVariant
                                 = PAGED_OPTIMIZED,
                                 interpret: bool = False):
    """Single-token GQA decode attention over a paged KV pool.

    Args:
      q: ``[batch, q_heads, head_dim]``.
      k_pages, v_pages: ``[num_pages, page_size, kv_heads, head_dim]``
        global block pool (shared by every request).
      page_table: ``[batch, pages_per_seq]`` int32 — logical page ``j`` of
        request ``b`` lives in physical page ``page_table[b, j]``.
      kv_len: ``[batch]`` int32 valid lengths (default: the full table).

    Returns ``[batch, q_heads, head_dim]``; bitwise it computes attention
    over the gathered cache ``k_pages[page_table]`` — table entries at or
    past ``kv_len`` may point anywhere valid (the engine points them at a
    trap page) and are fully masked.
    """
    b, hq, dh = q.shape
    _, page, hkv, _ = k_pages.shape
    n_pt = page_table.shape[1]
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (dh ** 0.5)
    if kv_len is None:
        kv_len = jnp.full((b,), n_pt * page, jnp.int32)

    g_pad = round_up(group, 8)
    q4 = q.reshape(b, hkv, group, dh)
    if g_pad != group:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    q3 = q4.reshape(b * hkv, g_pad, dh)
    # [P, hkv, page, dh]: one (physical page, head) pair per block fetch
    k4 = jnp.swapaxes(k_pages, 1, 2)
    v4 = jnp.swapaxes(v_pages, 1, 2)
    flat_pt = page_table.reshape(-1).astype(jnp.int32)   # [b * n_pt]

    kern = functools.partial(
        _paged_kernel, page=page, hkv=hkv, sm_scale=sm_scale,
        use_reciprocal=variant.use_reciprocal, mask_oob=variant.mask_oob)

    def kv_map(i, j, pt_ref, len_ref):
        # gather through the scalar-prefetched table: logical page j of
        # request i // hkv -> physical block index
        return (pt_ref[(i // hkv) * n_pt + j], i % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page table + kv_len
        grid=(b * hkv, n_pt),
        in_specs=[
            pl.BlockSpec((1, g_pad, dh), lambda i, j, pt, ln: (i, 0, 0)),
            pl.BlockSpec((1, 1, page, dh), kv_map),
            pl.BlockSpec((1, 1, page, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, g_pad, dh), lambda i, j, pt, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, dh), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g_pad, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat_pt, kv_len.astype(jnp.int32), q3, k4, v4)

    return out.reshape(b, hkv, g_pad, dh)[:, :, :group].reshape(b, hq, dh)


def paged_cost(variant: PagedFlashDecodeVariant, *, batch: int, q_heads: int,
               kv_heads: int, head_dim: int, seq: int, dtype,
               mean_kv_len: float | None = None):
    """Analytic cost: the split-KV cost at chunk=page_size plus the page
    table reads (SMEM-prefetched, but they still cross HBM once)."""
    proxy = FlashDecodeVariant(chunk=variant.page_size,
                               use_reciprocal=variant.use_reciprocal,
                               mask_oob=variant.mask_oob)
    c = cost(proxy, batch=batch, q_heads=q_heads, kv_heads=kv_heads,
             head_dim=head_dim, seq=seq, dtype=dtype,
             mean_kv_len=mean_kv_len)
    n_pt = round_up(seq, variant.page_size) // variant.page_size
    c = dataclasses.replace(c, hbm_bytes=c.hbm_bytes + batch * n_pt * 4)
    c.validate()
    return c


def _page_kv(k, v, page: int):
    """Pack a contiguous ``[b, s, hkv, d]`` cache into a shuffled physical
    pool + page table (the search harness's stand-in for the engine's
    allocator — a fixed permutation so the gather path is really exercised).
    """
    import numpy as np
    b, s, hkv, dh = k.shape
    s_pad = round_up(s, page)
    if s_pad != s:
        padw = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
    n_pt = s_pad // page
    perm = jnp.asarray(np.random.default_rng(17).permutation(b * n_pt),
                       jnp.int32)
    k_flat = k.reshape(b * n_pt, page, hkv, dh)
    v_flat = v.reshape(b * n_pt, page, hkv, dh)
    k_pages = jnp.zeros_like(k_flat).at[perm].set(k_flat)
    v_pages = jnp.zeros_like(v_flat).at[perm].set(v_flat)
    return k_pages, v_pages, perm.reshape(b, n_pt)


def _paged_run(variant, q, k, v, kv_len, *, interpret=True):
    page = min(variant.page_size, round_up(k.shape[1], 8))
    k_pages, v_pages, table = _page_kv(k, v, page)
    return paged_flash_decode_attention(q, k_pages, v_pages, table,
                                        kv_len=kv_len, variant=variant,
                                        interpret=interpret)


PAGED_SUITE_SHAPES = (
    {"batch": 2, "q_heads": 8, "kv_heads": 2, "head_dim": 64, "seq": 256},
    {"batch": 4, "q_heads": 4, "kv_heads": 4, "head_dim": 64, "seq": 512},
)


@register_kernel_space
def _paged_space() -> KernelSpace:
    return KernelSpace(
        name="paged_flash_decode",
        baseline=PAGED_BASELINE,
        default=PAGED_OPTIMIZED,
        run=_paged_run,
        oracle=_oracle,       # paging + gather must reproduce contiguous
        cost=paged_cost,
        knobs=(
            Knob("page_size", "pow2", 8, 256,
                 attacks=("overhead", "memory"),
                 note="KV pool granule = rows per grid step; small pages "
                      "cut allocator fragmentation, large pages cut "
                      "grid/DMA overhead"),
            Knob("mask_oob", "bool", attacks=("memory", "compute"),
                 target=True,
                 note="predicate logical pages past kv_len"),
            Knob("use_reciprocal", "bool", attacks=("compute",), target=True),
        ),
        suite_shapes=PAGED_SUITE_SHAPES,
        make_inputs=make_inputs,
    )
