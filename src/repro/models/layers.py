"""Shared model layers — all architectures are built from these.

Every layer calls the kernel package through ``repro.kernels.ops`` (never a
Pallas kernel directly): that is the "SGLang role" — the framework consumes
whatever variant Astra last reintegrated. On the CPU dry-run host the ops
dispatch to the pure-jnp references, which lower/differentiate cleanly; on
a TPU backend the serving paths pick up the Pallas kernels.

Training attention is an online-softmax scan over KV chunks (FlashAttention
schedule in pure jnp): activation memory O(seq x chunk) instead of
O(seq^2), which is what makes the 32k-prefill cells lowerable. Causal
masking is applied inside each chunk; fully-masked chunks still execute
(SPMD cannot skip) — the §Roofline "useful-FLOPs ratio" accounts for this
and the TPU-target Pallas path (splash-style skipping) is costed there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.sharding import tp


# ---------------------------------------------------------------------------
# initialization helpers: params + logical-axes trees are built together
# ---------------------------------------------------------------------------

def dense_init(key, shape, axes, scale=None, dtype=jnp.float32):
    """(array, logical_axes) — truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            .astype(dtype) * scale, axes)


def ones_init(shape, axes, dtype=jnp.float32):
    return jnp.ones(shape, dtype), axes


def zeros_init(shape, axes, dtype=jnp.float32):
    return jnp.zeros(shape, dtype), axes


def split_tree(pairs: dict):
    """{name: (array, axes)} -> (params dict, axes dict)."""
    params, axes = {}, {}
    for k, v in pairs.items():
        if isinstance(v, dict):
            params[k], axes[k] = split_tree(v)
        else:
            params[k], axes[k] = v
    return params, axes


# ---------------------------------------------------------------------------
# norms / rope / embeddings
# ---------------------------------------------------------------------------

def _current_mesh():
    """The mesh set by ``jax.set_mesh``, or None — also inside the serving
    engine's ``shard_map`` bodies, whose axes are manual and whose
    placement ``sharding.tp`` owns."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty or m.manual_axes else m


def shard_batch(x, n_batch_dims: int = 1):
    """Constrain the leading dim(s) of an activation to the batch mesh axes.

    GSPMD's propagation, given 2-D-sharded FSDP weights, often prefers to
    keep weights sharded and REPLICATE activations over the data axis —
    catastrophic at batch 256 x 4k. Pinning activations batch-sharded at
    block boundaries makes the solver insert the per-layer weight
    all-gathers instead (the FSDP pattern). No-op off-mesh (smoke tests).
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    if not axes:
        return x
    import numpy as np
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if x.shape[0] % size != 0:
        axes = ("data",) if "data" in mesh.axis_names \
            and x.shape[0] % mesh.shape["data"] == 0 else ()
        if not axes:
            return x
    lead = axes if len(axes) > 1 else axes[0]
    spec = jax.sharding.PartitionSpec(
        lead, *([None] * (x.ndim - 1)))
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def rms_norm(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def add_rms_norm(x, residual, w, eps=1e-6):
    """Fused residual-add + RMSNorm — paper Kernel 2 via ops dispatch."""
    return ops.fused_add_rmsnorm(x, residual, w, eps)


def rope(x, positions, theta=10000.0):
    """Rotary embedding. x: [..., seq, heads, head_dim], positions: [..., seq]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [.., S, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def embed_tokens(embedding, tokens):
    return jnp.take(embedding, tokens, axis=0)


def unembed(x, lm_head):
    # vocab-sharded under an active serving TP plan: the local partial
    # covers a contiguous vocab slice, all-gathered back to full order
    return tp.gather_vocab(
        jnp.einsum("...d,dv->...v", x, lm_head.astype(x.dtype)))


def ce_loss(logits, labels, vocab: int):
    """Vocab-shard-friendly cross-entropy.

    ``take_along_axis`` over a model-sharded vocab axis forces GSPMD to
    all-gather the logits ([B,S,V] fp32 — gigabytes); selecting via an
    iota==label mask keeps every op elementwise/reduce, so the vocab axis
    stays sharded and the reduce lowers to a psum. (§Perf iteration 1.)
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    sel = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                   logits.ndim - 1) == labels[..., None]
    gold = jnp.sum(jnp.where(sel, logits, 0.0), axis=-1)
    mask = (labels >= 0) & (labels < vocab)
    nll = jnp.where(mask, logz - gold, 0.0)
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def qkv_proj(p, x, cfg: ModelConfig):
    """x: [B, S, D] -> q [B,S,Hq,dh], k/v [B,S,Hkv,dh].

    Projection weights keep the head axis EXPLICIT ([D, H, dh]) so the
    sharding rules can only shard whole heads over the model axis — a
    flattened [D, H*dh] output dim lets GSPMD split head_dim itself, which
    turns every attention contraction into a partial-sum all-reduce of the
    score tensor (§Perf iteration 4).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def out_proj(p, o, dtype):
    """o: [B, S, Hq, dh] -> [B, S, D] via wo [Hq, dh, D].

    Under an active serving TP plan the incoming heads are a local
    shard; they are all-gathered (concatenated, no partial sums) before
    the replicated ``wo`` contraction so the result stays bit-identical
    to the single-device einsum."""
    o = tp.gather_heads(o)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dtype))


def shard_attention(q, k, v):
    """Pick the attention parallelism per arch (§Perf iteration 5).

    * heads divide the model axis -> tensor-parallel heads (classic TP);
    * otherwise -> CONTEXT parallelism: shard q (and the output) along the
      sequence axis over the model axis; K/V stay replicated and stream
      through every chip's flash scan. Without this, archs whose head count
      doesn't divide the mesh (qwen2: 14, yi: 56) recompute full attention
      on all 16 model-axis chips.
    """
    mesh = _current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return q, k, v
    import numpy as np
    m = mesh.shape["model"]
    P, NS = jax.sharding.PartitionSpec, jax.sharding.NamedSharding
    batch = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    if q.shape[0] % int(np.prod([mesh.shape[a] for a in batch])):
        batch = ()
    b_ax = (batch if len(batch) > 1 else (batch[0] if batch else None))
    hq, hkv = q.shape[2], k.shape[2]
    if hq % m == 0 and hkv % m == 0:
        spec = P(b_ax, None, "model", None)
        return (lax.with_sharding_constraint(q, NS(mesh, spec)),
                lax.with_sharding_constraint(k, NS(mesh, spec)),
                lax.with_sharding_constraint(v, NS(mesh, spec)))
    if q.shape[1] % m == 0:
        qs = lax.with_sharding_constraint(
            q, NS(mesh, P(b_ax, "model", None, None)))
        kv = P(b_ax, None, None, None)
        return (qs, lax.with_sharding_constraint(k, NS(mesh, kv)),
                lax.with_sharding_constraint(v, NS(mesh, kv)))
    return q, k, v


def attn_params(key, cfg: ModelConfig, dtype):
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    pairs = {
        "wq": dense_init(ks[0], (d, hq, dh), ("embed", "heads", "head_dim"),
                         scale=d ** -0.5, dtype=dtype),
        "wk": dense_init(ks[1], (d, hkv, dh),
                         ("embed", "kv_heads", "head_dim"),
                         scale=d ** -0.5, dtype=dtype),
        "wv": dense_init(ks[2], (d, hkv, dh),
                         ("embed", "kv_heads", "head_dim"),
                         scale=d ** -0.5, dtype=dtype),
        "wo": dense_init(ks[3], (hq, dh, d), ("heads", "head_dim", "embed"),
                         scale=(hq * dh) ** -0.5, dtype=dtype),
    }
    if cfg.qkv_bias:
        pairs["bq"] = zeros_init((hq, dh), ("heads", "head_dim"), dtype)
        pairs["bk"] = zeros_init((hkv, dh), ("kv_heads", "head_dim"), dtype)
        pairs["bv"] = zeros_init((hkv, dh), ("kv_heads", "head_dim"), dtype)
    if cfg.qk_norm:
        pairs["q_norm"] = ones_init((dh,), ("head_dim",))
        pairs["k_norm"] = ones_init((dh,), ("head_dim",))
    return pairs


def chunked_attention(q, k, v, *, causal=True, window=None, chunk=512,
                      q_offset=0, kv_len=None, cross=False):
    """Online-softmax attention, scanned over KV chunks.

    q: [B, Sq, Hq, dh]; k, v: [B, Skv, Hkv, dh]. GQA via head grouping.
    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``kv_len``: [B] valid KV length (decode with padded caches).
    ``cross``: no causal mask (encoder-decoder cross attention).
    Returns [B, Sq, Hq, dh].
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = dh ** -0.5
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, dh) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, idx):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(kf, idx * chunk, chunk, axis=1)
        vs = lax.dynamic_slice_in_dim(vf, idx * chunk, chunk, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, ks)
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((sq, chunk), bool) if cross else \
            (k_pos[None, :] <= q_pos[:, None])
        if window is not None and not cross:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        mask = jnp.broadcast_to(mask[None], (b, sq, chunk))
        if kv_len is not None:
            mask &= (k_pos[None, None, :] < kv_len[:, None, None])
        s = jnp.where(mask[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd",
                                                      p, vs)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.swapaxes(out, 2, 3).reshape(b, hkv, sq, g * dh)  # merge heads
    out = jnp.swapaxes(out, 1, 2).reshape(b, sq, hq * dh)
    return out.astype(q.dtype).reshape(b, sq, hq, dh)


# ---------------------------------------------------------------------------
# flash attention with recomputing backward (custom VJP)
#
# The naive scan saves every chunk's probability matrix as a residual —
# stacked [n_chunks, B, Hkv, G, Sq, chunk] fp32 buffers that dominate HBM
# traffic and temp memory (§Perf iteration 3). FlashAttention's backward
# recomputes p = exp(qk - lse) per chunk instead. Everything runs under
# jax.named_scope("flash_kernel"): on the TPU target this region IS one
# fused Pallas kernel (interior tensors live in VMEM), and the roofline
# parser costs the region analytically (see roofline/hlo_parser.py).
# ---------------------------------------------------------------------------

def _flash_mask(q_pos, k_pos, *, causal, window):
    mask = None
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask


def _flash_fwd_scan(qf, kf, vf, *, causal, window, chunk):
    """qf: [B,Hkv,G,Sq,D] pre-scaled fp32; kf/vf: [B,Skv,Hkv,D] fp32.
    Returns (acc, m, l)."""
    b, hkv, g, sq, dh = qf.shape
    skv = kf.shape[1]
    n_chunks = skv // chunk
    q_pos = jnp.arange(sq)

    def body(carry, idx):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(kf, idx * chunk, chunk, axis=1)
        vs = lax.dynamic_slice_in_dim(vf, idx * chunk, chunk, axis=1)
        s = jnp.einsum("bhgqd,bkhd->bhgqk", qf, ks)
        mask = _flash_mask(q_pos, idx * chunk + jnp.arange(chunk),
                           causal=causal, window=window)
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vs)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=None, chunk=512,
                    cross=False):
    """Memory-efficient attention. q: [B,Sq,Hq,D], k/v: [B,Skv,Hkv,D]."""
    out, _ = _flash_forward(q, k, v, causal, window, chunk, cross)
    return out


def _prep(q, k, v, chunk):
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, dh) * (dh ** -0.5)
    qf = qf.transpose(0, 2, 3, 1, 4)                      # [B,Hkv,G,Sq,D]
    return qf, k.astype(jnp.float32), v.astype(jnp.float32), chunk, pad


def _unprep(acc, b, sq, hq, dh):
    # [B,Hkv,G,Sq,D] -> [B,Sq,Hq,D]
    return acc.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh)


def _flash_forward(q, k, v, causal, window, chunk, cross):
    with jax.named_scope("flash_kernel"):
        b, sq, hq, dh = q.shape
        qf, kf, vf, chunk, _ = _prep(q, k, v, chunk)
        acc, m, l = _flash_fwd_scan(qf, kf, vf,
                                    causal=causal and not cross,
                                    window=window if not cross else None,
                                    chunk=chunk)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        out = _unprep(acc / jnp.maximum(l, 1e-30)[..., None],
                      b, sq, hq, dh).astype(q.dtype)
    return out, (q, k, v, out, lse)


def _flash_backward(causal, window, chunk, cross, res, dout):
    q, k, v, out, lse = res
    with jax.named_scope("flash_kernel"):
        b, sq, hq, dh = q.shape
        skv = k.shape[1]
        hkv = k.shape[2]
        g = hq // hkv
        qf, kf, vf, chunk, pad = _prep(q, k, v, chunk)
        dof = dout.astype(jnp.float32).reshape(b, sq, hkv, g, dh) \
            .transpose(0, 2, 3, 1, 4)                     # [B,Hkv,G,Sq,D]
        of = out.astype(jnp.float32).reshape(b, sq, hkv, g, dh) \
            .transpose(0, 2, 3, 1, 4)
        delta = jnp.sum(dof * of, axis=-1)                # [B,Hkv,G,Sq]
        is_causal = causal and not cross
        win = window if not cross else None
        q_pos = jnp.arange(sq)
        n_chunks = kf.shape[1] // chunk

        def body(dq, idx):
            ks = lax.dynamic_slice_in_dim(kf, idx * chunk, chunk, axis=1)
            vs = lax.dynamic_slice_in_dim(vf, idx * chunk, chunk, axis=1)
            s = jnp.einsum("bhgqd,bkhd->bhgqk", qf, ks)
            mask = _flash_mask(q_pos, idx * chunk + jnp.arange(chunk),
                               causal=is_causal, window=win)
            if mask is not None:
                s = jnp.where(mask[None, None, None], s, -1e30)
            p = jnp.exp(s - lse[..., None])               # recomputed
            dp = jnp.einsum("bhgqd,bkhd->bhgqk", dof, vs)
            ds = p * (dp - delta[..., None])
            dq = dq + jnp.einsum("bhgqk,bkhd->bhgqd", ds, ks)
            dk_j = jnp.einsum("bhgqk,bhgqd->bkhd", ds, qf)
            dv_j = jnp.einsum("bhgqk,bhgqd->bkhd", p, dof)
            return dq, (dk_j, dv_j)

        dq0 = jnp.zeros_like(qf)
        dqf, (dks, dvs) = lax.scan(body, dq0, jnp.arange(n_chunks))
        dq = _unprep(dqf * (dh ** -0.5), b, sq, hq, dh).astype(q.dtype)
        dk = jnp.moveaxis(dks, 0, 1).reshape(b, n_chunks * chunk, hkv, dh)
        dv = jnp.moveaxis(dvs, 0, 1).reshape(b, n_chunks * chunk, hkv, dh)
        if pad:
            dk, dv = dk[:, :skv], dv[:, :skv]
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(
    lambda q, k, v, causal, window, chunk, cross:
        _flash_forward(q, k, v, causal, window, chunk, cross),
    _flash_backward)


def attention_block(p, x, cfg: ModelConfig, *, positions=None, chunk=512):
    """Full-sequence (training/prefill) self-attention sublayer body."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = qkv_proj(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q, k, v = shard_attention(q, k, v)
    o = flash_attention(q, k, v, True, cfg.window, chunk, False)
    return out_proj(p, o, x.dtype), (k, v)


def prefix_attention(q, k_new, v_new, k_prefix, v_prefix, prefix_len):
    """Suffix-prefill attention over a two-segment KV: cached prefix rows
    followed by the suffix's own keys/values.

    q, k_new, v_new: [B, S, H*, dh] — the suffix (right-padded to its
    bucket); k_prefix, v_prefix: [B, P, Hkv, dh] — prefix rows gathered
    from the paged pool, of which only the first ``prefix_len`` (traced
    i32) are valid; the tail is trap-page garbage and must be masked, which
    is why ``chunked_attention``'s single ``kv_len`` cut-off cannot express
    this layout. Causality is over *absolute* positions: suffix query i
    sits at ``prefix_len + i`` and sees the valid prefix plus suffix keys
    ``<= i``.
    """
    b, s, hq, dh = q.shape
    hkv = k_new.shape[2]
    g = hq // hkv
    p_rows = k_prefix.shape[1]
    k = jnp.concatenate([k_prefix, k_new], 1).astype(jnp.float32)
    v = jnp.concatenate([v_prefix, v_new], 1).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, s, hkv, g, dh) * dh ** -0.5
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k)
    kpos = jnp.arange(p_rows + s)
    qpos = jnp.asarray(prefix_len, jnp.int32) + jnp.arange(s)
    valid = (kpos < prefix_len) | (kpos >= p_rows)
    pos_of_k = jnp.where(kpos < p_rows, kpos, prefix_len + (kpos - p_rows))
    mask = valid[None, :] & (pos_of_k[None, :] <= qpos[:, None])
    sc = jnp.where(mask[None, None, None], sc, -1e30)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", jax.nn.softmax(sc, -1), v)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU) — paper Kernel 3 consumer
# ---------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig, dtype, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {
        "w_gateup": dense_init(k1, (cfg.d_model, 2 * d_ff),
                               ("embed", "mlp"), dtype=dtype),
        "w_down": dense_init(k2, (d_ff, cfg.d_model),
                             ("mlp", "embed"), dtype=dtype),
    }


def mlp_block(p, x):
    """SwiGLU: gate/up fused matmul -> silu_and_mul kernel -> down proj.

    Under an active serving TP plan ``w_gateup`` columns are sharded
    (pre-permuted so each shard holds its own gate/up pair, see
    ``sharding.tp.permute_gateup``); the local ``silu_and_mul``
    outputs are all-gathered before the replicated down projection."""
    h = jnp.einsum("bsd,df->bsf", x, p["w_gateup"].astype(x.dtype))
    h = ops.silu_and_mul(h)
    h = tp.gather_mlp(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# decode-time attention over a KV cache (single new token)
# ---------------------------------------------------------------------------

def decode_attention(p, x, cache_k, cache_v, pos, cfg: ModelConfig, *,
                     kv_len=None, seq_shard_axis=None):
    """One-token decode self-attention.

    x: [B, 1, D]; cache_k/v: [B, S, Hkv, dh] (already containing this
    token's k/v at position ``pos``); pos: [B] absolute positions.
    When ``seq_shard_axis`` is set (inside shard_map), the KV cache is
    sequence-sharded: each shard computes a partial (V, LSE) with the flash
    decode kernel and partials merge with the Kernel-1 LSE math via
    collectives — the distributed form of merge_attn_states_lse.
    """
    b = x.shape[0]
    q, k_new, v_new = qkv_proj(p, x, cfg)          # [B,1,H,dh]
    q = rope(q, pos[:, None], cfg.rope_theta)
    q = q[:, 0]                                    # [B,Hq,dh]
    if kv_len is None:
        kv_len = pos + 1

    if seq_shard_axis is None:
        o = ops.flash_decode_attention(q, cache_k, cache_v, kv_len=kv_len)
    else:
        # split-KV across devices: local partial + distributed LSE merge
        axis = seq_shard_axis
        idx = lax.axis_index(axis)
        shard = cache_k.shape[1]
        local_len = jnp.clip(kv_len - idx * shard, 0, shard)
        o_part, lse = ops.flash_decode_attention(
            q, cache_k, cache_v, kv_len=local_len, return_lse=True)
        o_part = jnp.where(jnp.isneginf(lse)[..., None], 0.0,
                           o_part.astype(jnp.float32))
        m = lax.pmax(lse, axis)
        m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
        w = jnp.exp(lse - m_safe)
        w = jnp.where(jnp.isneginf(lse), 0.0, w)
        num = lax.psum(w[..., None] * o_part, axis)
        den = lax.psum(w, axis)
        o = (num / jnp.maximum(den, 1e-30)[..., None]).astype(x.dtype)

    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(x.dtype))
    return out, (k_new[:, 0], v_new[:, 0])


def update_cache(cache_k, cache_v, k_new, v_new, pos):
    """Write one token's K/V at [b, pos[b]] via scatter.

    A one-hot multiply-add formulation reads+writes the ENTIRE cache
    (~6 cache round trips per layer per step); the scatter touches one row
    per sequence. GSPMD keeps the batch dim sharded and masks the
    (possibly sharded) sequence dim — decode_32k's memory term dropped
    ~8x with this (§Perf hillclimb C, EXPERIMENTS.md).
    """
    b = cache_k.shape[0]
    idx = jnp.arange(b)
    return (cache_k.at[idx, pos].set(k_new.astype(cache_k.dtype)),
            cache_v.at[idx, pos].set(v_new.astype(cache_v.dtype)))
