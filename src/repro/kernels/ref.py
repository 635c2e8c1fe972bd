"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the *contracts*: each Pallas kernel (any variant) must match its
oracle to tolerance on every test input. They mirror the SGLang kernel
semantics described in the paper (Table 1):

  Kernel 1  merge_attn_states_lse:
      V_out = (e^{S_a} V_a + e^{S_b} V_b) / (e^{S_a} + e^{S_b})
      S_out = log(e^{S_a} + e^{S_b})
  Kernel 2  fused_add_rmsnorm:
      r' = x + r ;  y = r' / sqrt(mean(r'^2) + eps) * w
  Kernel 3  silu_and_mul:
      out = SiLU(gate) * up,  SiLU(z) = z / (1 + e^{-z})

All oracles are numerically-stable fp32 formulations (computation in fp32,
cast back to the input dtype), matching SGLang's accumulate-in-fp32 policy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def merge_attn_states_lse(
    v_a: jax.Array,
    s_a: jax.Array,
    v_b: jax.Array,
    s_b: jax.Array,
):
    """Merge two partial attention states with log-sum-exp weights.

    Args:
      v_a, v_b: partial attention outputs ``[..., head_dim]``.
      s_a, s_b: log-sum-exp of the corresponding softmax partitions,
        shape ``[...]`` (i.e. ``v.shape[:-1]``). ``-inf`` marks an empty
        partition and is handled exactly (the other side wins).

    Returns:
      (v_out, s_out) with the same shapes/dtypes as the inputs.
    """
    out_dtype = v_a.dtype
    sa = s_a.astype(jnp.float32)
    sb = s_b.astype(jnp.float32)
    va = v_a.astype(jnp.float32)
    vb = v_b.astype(jnp.float32)

    m = jnp.maximum(sa, sb)
    # Guard the fully-empty case (both -inf): weights become 0, s_out -inf.
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    wa = jnp.exp(sa - m_safe)
    wb = jnp.exp(sb - m_safe)
    denom = wa + wb
    inv = jnp.where(denom > 0, 1.0 / denom, 0.0)
    a = (wa * inv)[..., None]
    b = (wb * inv)[..., None]
    v_out = a * va + b * vb
    s_out = m + jnp.log(denom)
    return v_out.astype(out_dtype), s_out.astype(s_a.dtype)


def fused_add_rmsnorm(
    x: jax.Array,
    residual: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
):
    """Fused residual-add + RMSNorm (SGLang contract).

    Args:
      x:        ``[..., d]`` block output to be added into the residual.
      residual: ``[..., d]`` running residual stream.
      weight:   ``[d]`` scale.

    Returns:
      (y, new_residual): the normalized output and the updated residual
      (``x + residual``), both in the input dtype.
    """
    out_dtype = x.dtype
    r = x.astype(jnp.float32) + residual.astype(jnp.float32)
    var = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
    y = r * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return y.astype(out_dtype), r.astype(out_dtype)


def silu_and_mul(x: jax.Array) -> jax.Array:
    """SwiGLU gate: ``silu(x[..., :d]) * x[..., d:]`` with ``d = x.shape[-1]//2``."""
    d = x.shape[-1] // 2
    gate = x[..., :d].astype(jnp.float32)
    up = x[..., d:].astype(jnp.float32)
    out = gate * jax.nn.sigmoid(gate) * up
    return out.astype(x.dtype)


def flash_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kv_len: jax.Array | None = None,
    sm_scale: float | None = None,
):
    """Oracle for single-token GQA decode attention.

    Args:
      q: ``[batch, q_heads, head_dim]`` query for ONE new token.
      k: ``[batch, seq, kv_heads, head_dim]`` key cache.
      v: ``[batch, seq, kv_heads, head_dim]`` value cache.
      kv_len: optional ``[batch]`` int32 valid lengths (entries >= kv_len are
        masked out; a length of 0 gives zeros). Defaults to the full cache.
      sm_scale: softmax scale; defaults to ``1/sqrt(head_dim)``.

    Returns:
      ``[batch, q_heads, head_dim]`` attention output in q's dtype.
    """
    b, hq, dh = q.shape
    _, s, hkv, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (dh ** 0.5)

    # NOTE: no whole-cache .astype — XLA would hoist an fp32 copy of the
    # full [b, s, hkv, dh] cache out of the decode loop (2x HBM + traffic).
    # bf16 reads with fp32 accumulation via preferred_element_type instead.
    qf = q.reshape(b, hkv, group, dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if kv_len is not None:
        mask = jnp.arange(s)[None, :] < kv_len[:, None]  # [b, s]
        scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if kv_len is not None:
        # an all-masked row's softmax is NaN; it attends to nothing
        probs = jnp.where(mask[:, None, None, :], probs, 0.0)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, dh).astype(q.dtype)


def paged_flash_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    *,
    kv_len: jax.Array | None = None,
    sm_scale: float | None = None,
):
    """Oracle for paged decode attention: gather the logical cache through
    the page table, then contiguous decode attention.

    Args:
      q: ``[batch, q_heads, head_dim]``.
      k_pages, v_pages: ``[num_pages, page_size, kv_heads, head_dim]``
        global page pool.
      page_table: ``[batch, pages_per_seq]`` int32 physical page indices.
      kv_len: optional ``[batch]`` valid lengths; rows at or past ``kv_len``
        (including whatever trap/garbage pages the tail of the table points
        at) are masked out exactly.

    The gathered array ``k_pages[page_table]`` reshapes to the contiguous
    ``[batch, pages_per_seq * page_size, kv_heads, head_dim]`` cache, so the
    paged path is bit-identical to contiguous attention over the same rows.
    """
    b = q.shape[0]
    _, page, hkv, dh = k_pages.shape
    n_pt = page_table.shape[1]
    k = k_pages[page_table].reshape(b, n_pt * page, hkv, dh)
    v = v_pages[page_table].reshape(b, n_pt * page, hkv, dh)
    return flash_decode_attention(q, k, v, kv_len=kv_len, sm_scale=sm_scale)


def flash_decode_lse(
    q: jax.Array,
    k: jax.Array,
    *,
    kv_len: jax.Array | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """LSE of the decode-attention softmax: ``[batch, q_heads]`` fp32.

    This is the ``S`` half of the partial state ``(V, S)`` consumed by
    ``merge_attn_states_lse`` in the distributed split-KV decode path.
    """
    b, hq, dh = q.shape
    _, s, hkv, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (dh ** 0.5)
    qf = q.reshape(b, hkv, group, dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if kv_len is not None:
        mask = jnp.arange(s)[None, :] < kv_len[:, None]
        scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)  # [b, hkv, group]
    return lse.reshape(b, hq)
