"""Dense decoder-only transformer (llama-family).

Covers qwen2-0.5b (GQA kv=2 + QKV bias), yi-34b, qwen3-8b (qk-norm),
h2o-danube-1.8b (sliding window), and the chameleon-34b backbone
(early-fusion VQ tokens are ordinary ids in the unified vocab).

Structure: scan-over-layers with stacked parameter pytrees (HLO depth
O(1)), per-layer remat, and the SGLang fused-add-RMSNorm residual pattern —
each block consumes paper Kernel 2 twice and Kernel 3 once, decode consumes
the flash-decode kernel whose combiner is paper Kernel 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import tp

# Right-padding a prompt to a bucketed length is safe here: the cache is
# positional K/V and attention is causal, so pad positions can never
# influence positions < length, and decode's kv_len mask hides them until
# they are overwritten in place. The serving engine keys bucketed prefill
# admission on this flag.
PAD_PREFILL = True

# Paged-KV serving is exact here: the cache is positional K/V, decode is
# per-slot independent (no cross-request coupling in any op), and recompute
# preemption — re-prefilling prompt + generated prefix — reproduces the
# straight-through stream under greedy sampling. Families with recurrent
# state (xlstm/hybrid), cross-attention caches (encdec), or slot-coupled
# routing (moe capacity) keep the contiguous pool. Rolling-window archs
# (cfg.window) are excluded by ``registry.paged_ok``: their cache is already
# bounded and its pos%window layout does not page.
PAGED_OK = True


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init(cfg: ModelConfig, key) -> tuple[dict, dict]:
    """Returns (params, logical_axes). Layer params are stacked on axis 0."""
    keys = jax.random.split(key, 4)
    dtype = jnp.float32  # master weights; compute casts per-use

    def one_layer(k):
        ka, km = jax.random.split(k)
        pairs = {
            "attn": L.attn_params(ka, cfg, dtype),
            "mlp": L.mlp_params(km, cfg, dtype),
            "attn_norm": L.ones_init((cfg.d_model,), ("embed",)),
            "mlp_norm": L.ones_init((cfg.d_model,), ("embed",)),
        }
        return L.split_tree(pairs)

    layer_keys = jax.random.split(keys[0], cfg.n_layers)
    stacked = jax.vmap(lambda k: one_layer(k)[0])(layer_keys)
    _, axes_one = one_layer(layer_keys[0])
    layer_axes = jax.tree.map(lambda ax: ("layers",) + ax, axes_one,
                              is_leaf=lambda x: isinstance(x, tuple))

    emb, emb_ax = L.dense_init(keys[1], (cfg.padded_vocab, cfg.d_model),
                               ("embed_vocab", "mlp"), scale=1.0, dtype=dtype)
    head, head_ax = L.dense_init(keys[2], (cfg.d_model, cfg.padded_vocab),
                                 ("embed", "vocab"), dtype=dtype)
    fnorm, fnorm_ax = L.ones_init((cfg.d_model,), ("embed",))
    params = {"embed": emb, "layers": stacked, "final_norm": fnorm,
              "lm_head": head}
    axes = {"embed": emb_ax, "layers": layer_axes, "final_norm": fnorm_ax,
            "lm_head": head_ax}
    return params, axes


# --------------------------------------------------------------------------
# training / prefill forward
# --------------------------------------------------------------------------

def _block_train(p_layer, carry, cfg: ModelConfig, chunk: int):
    hidden, residual = carry
    hidden = L.shard_batch(hidden)
    residual = L.shard_batch(residual)
    normed, residual = L.add_rms_norm(hidden, residual,
                                      p_layer["attn_norm"], cfg.norm_eps)
    attn_out, _ = L.attention_block(p_layer["attn"], normed, cfg, chunk=chunk)
    normed, residual = L.add_rms_norm(attn_out, residual,
                                      p_layer["mlp_norm"], cfg.norm_eps)
    hidden = L.mlp_block(p_layer["mlp"], normed)
    return hidden, residual


def forward(params, cfg: ModelConfig, tokens, *, chunk: int = 512):
    """Teacher-forced logits [B, S, V_pad] (compute dtype = cfg.dtype)."""
    hidden = L.embed_tokens(params["embed"], tokens).astype(cfg.jnp_dtype)
    residual = jnp.zeros_like(hidden)

    block = jax.checkpoint(
        functools.partial(_block_train, cfg=cfg, chunk=chunk),
        policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, p_layer):
        return block(p_layer, carry), None

    (hidden, residual), _ = lax.scan(body, (hidden, residual),
                                     params["layers"])
    normed, _ = L.add_rms_norm(hidden, residual, params["final_norm"],
                               cfg.norm_eps)
    return L.unembed(normed, params["lm_head"])


def loss_fn(params, cfg: ModelConfig, batch, *, chunk: int = 512):
    """Next-token cross-entropy; batch = {"tokens", "labels"} of [B, S]."""
    logits = forward(params, cfg, batch["tokens"], chunk=chunk)
    return L.ce_loss(logits, batch["labels"], cfg.vocab)


# --------------------------------------------------------------------------
# serving: prefill + single-token decode over a KV cache
# --------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, seq: int):
    """(ShapeDtypeStruct cache tree, logical axes). Sliding-window archs
    keep a rolling window-sized cache."""
    s = min(seq, cfg.window) if cfg.window else seq
    shape = (cfg.n_layers, batch, s, cfg.n_kv_heads, cfg.head_dim)
    axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return ({"k": jax.ShapeDtypeStruct(shape, cfg.jnp_dtype),
             "v": jax.ShapeDtypeStruct(shape, cfg.jnp_dtype)},
            {"k": axes, "v": axes})


def init_cache(cfg: ModelConfig, batch: int, seq: int):
    spec, axes = cache_spec(cfg, batch, seq)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec), axes


def paged_cache_spec(cfg: ModelConfig, num_pages: int, page_size: int):
    """Paged pool layout: the contiguous cache's (batch, kv_seq) axes become
    one global (pages, page) block pool shared by every request."""
    if cfg.window:
        raise ValueError("rolling-window caches do not page "
                         "(registry.paged_ok gates on cfg.window)")
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    axes = ("layers", "pages", "page", "kv_heads", "head_dim")
    return ({"k": jax.ShapeDtypeStruct(shape, cfg.jnp_dtype),
             "v": jax.ShapeDtypeStruct(shape, cfg.jnp_dtype)},
            {"k": axes, "v": axes})


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    spec, axes = paged_cache_spec(cfg, num_pages, page_size)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec), axes


def _block_prefill(p_layer, carry, cfg: ModelConfig, chunk: int):
    hidden, residual = carry
    hidden = L.shard_batch(hidden)
    residual = L.shard_batch(residual)
    normed, residual = L.add_rms_norm(hidden, residual,
                                      p_layer["attn_norm"], cfg.norm_eps)
    attn_out, (k, v) = L.attention_block(p_layer["attn"], normed, cfg,
                                         chunk=chunk)
    normed, residual = L.add_rms_norm(attn_out, residual,
                                      p_layer["mlp_norm"], cfg.norm_eps)
    hidden = L.mlp_block(p_layer["mlp"], normed)
    return (hidden, residual), (k, v)


def prefill(params, cfg: ModelConfig, tokens, *, chunk: int = 512,
            cache_len: int | None = None, length=None):
    """Process the prompt; returns (last-position logits, filled cache).
    ``cache_len`` pre-sizes the cache for subsequent decode_steps.
    ``length`` (traced i32 scalar) marks the true prompt length when
    ``tokens`` is right-padded to a bucket: logits are taken at position
    ``length - 1`` instead of the (pad) last position."""
    b, s = tokens.shape
    hidden = L.embed_tokens(params["embed"], tokens).astype(cfg.jnp_dtype)
    residual = jnp.zeros_like(hidden)

    block = jax.checkpoint(
        functools.partial(_block_prefill, cfg=cfg, chunk=chunk),
        policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, p_layer):
        carry, kv = block(p_layer, carry)
        return carry, kv

    (hidden, residual), (ks, vs) = lax.scan(body, (hidden, residual),
                                            params["layers"])
    if cfg.window and s > cfg.window:
        # rolling cache keeps the last `window` positions, laid out at
        # slot = pos % window
        w = cfg.window
        pos = jnp.arange(s - w, s)
        slots = pos % w
        order = jnp.argsort(slots)
        ks = ks[:, :, s - w:][:, :, order]
        vs = vs[:, :, s - w:][:, :, order]
    target = min(cache_len, cfg.window) if (cache_len and cfg.window) \
        else cache_len
    if target and target > ks.shape[2]:
        pad = ((0, 0), (0, 0), (0, target - ks.shape[2]), (0, 0), (0, 0))
        ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs}
    h_last, r_last = _last_position(hidden, residual, length)
    normed, _ = L.add_rms_norm(h_last, r_last,
                               params["final_norm"], cfg.norm_eps)
    return L.unembed(normed[:, 0], params["lm_head"]), cache


def prefill_suffix(params, cfg: ModelConfig, tokens, prefix, *,
                   prefix_len, length=None):
    """Prefill only the *suffix* of a prompt whose first ``prefix_len``
    positions are already cached (radix prefix hit).

    ``tokens``: [1, S] suffix ids, right-padded to the suffix bucket.
    ``prefix``: {"k","v"} of [L, 1, P, Hkv, dh] — prefix rows gathered
    from the paged pool (``registry.read_pages``); only the first
    ``prefix_len`` rows are valid, the tail is trap garbage that
    ``prefix_attention`` masks. ``prefix_len`` and ``length`` (true suffix
    length) are traced i32 scalars, so the compile key is the pair of
    bucket shapes only. Returns (logits at suffix position ``length - 1``,
    suffix KV {"k","v"} [L, 1, S, Hkv, dh]) for the page scatter."""
    if cfg.window:
        raise ValueError("rolling-window caches do not serve from the "
                         "paged pool, so they never suffix-prefill")
    b, s = tokens.shape
    hidden = L.embed_tokens(params["embed"], tokens).astype(cfg.jnp_dtype)
    residual = jnp.zeros_like(hidden)
    positions = jnp.asarray(prefix_len, jnp.int32) + \
        jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(carry, xs):
        p_layer, pk, pv = xs
        hidden, residual = carry
        hidden = L.shard_batch(hidden)
        residual = L.shard_batch(residual)
        normed, residual = L.add_rms_norm(hidden, residual,
                                          p_layer["attn_norm"], cfg.norm_eps)
        q, k, v = L.qkv_proj(p_layer["attn"], normed, cfg)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        q, k, v = L.shard_attention(q, k, v)
        attn = L.prefix_attention(q, k, v, pk, pv, prefix_len)
        attn_out = L.out_proj(p_layer["attn"], attn, normed.dtype)
        normed, residual = L.add_rms_norm(attn_out, residual,
                                          p_layer["mlp_norm"], cfg.norm_eps)
        hidden = L.mlp_block(p_layer["mlp"], normed)
        return (hidden, residual), (k, v)

    (hidden, residual), (ks, vs) = lax.scan(
        body, (hidden, residual),
        (params["layers"], prefix["k"], prefix["v"]))
    h_last, r_last = _last_position(hidden, residual, length)
    normed, _ = L.add_rms_norm(h_last, r_last,
                               params["final_norm"], cfg.norm_eps)
    return L.unembed(normed[:, 0], params["lm_head"]), {"k": ks, "v": vs}


def _last_position(hidden, residual, length):
    """[B,1,D] slices of the final prompt position (``length-1`` when the
    prompt is right-padded, else the literal last position)."""
    if length is None:
        return hidden[:, -1:], residual[:, -1:]
    idx = jnp.asarray(length, jnp.int32) - 1
    return (lax.dynamic_slice_in_dim(hidden, idx, 1, axis=1),
            lax.dynamic_slice_in_dim(residual, idx, 1, axis=1))


def decode_step(params, cfg: ModelConfig, cache, token, pos, *,
                seq_shard_axis=None):
    """One decode step. token: [B] ids; pos: [B] absolute positions.
    Returns (logits [B, V_pad], updated cache)."""
    hidden = L.embed_tokens(params["embed"], token[:, None]) \
        .astype(cfg.jnp_dtype)                                  # [B,1,D]
    residual = jnp.zeros_like(hidden)
    w = cfg.window
    slot = pos % w if w else pos
    kv_len = jnp.minimum(pos + 1, w) if w else pos + 1

    # The cache rides in the scan CARRY and is updated in place with
    # dynamic_update_index_in_dim — XLA aliases carry updates, so only the
    # touched layer slice moves. Passing it as scan xs/ys instead forces a
    # whole-cache read + write every decode step (§Perf hillclimb C).
    def body(carry, layer_in):
        p_layer, li = layer_in
        hidden, residual, ks, vs = carry
        k_l = lax.dynamic_index_in_dim(ks, li, 0, keepdims=False)
        v_l = lax.dynamic_index_in_dim(vs, li, 0, keepdims=False)
        normed, residual = L.add_rms_norm(hidden, residual,
                                          p_layer["attn_norm"], cfg.norm_eps)
        # project + rope the new token, write it into the cache first
        q, k_new, v_new = L.qkv_proj(p_layer["attn"], normed, cfg)
        q = L.rope(q, pos[:, None], cfg.rope_theta)
        k_new = L.rope(k_new, pos[:, None], cfg.rope_theta)
        k_l, v_l = L.update_cache(k_l, v_l, k_new[:, 0], v_new[:, 0], slot)
        ks = lax.dynamic_update_index_in_dim(ks, k_l, li, 0)
        vs = lax.dynamic_update_index_in_dim(vs, v_l, li, 0)
        o = _cached_attention(q[:, 0], k_l, v_l, kv_len, cfg,
                              seq_shard_axis)
        attn_out = L.out_proj(p_layer["attn"], o[:, None], o.dtype)
        normed, residual = L.add_rms_norm(attn_out, residual,
                                          p_layer["mlp_norm"], cfg.norm_eps)
        hidden = L.mlp_block(p_layer["mlp"], normed)
        return (hidden, residual, ks, vs), None

    (hidden, residual, ks, vs), _ = lax.scan(
        body, (hidden, residual, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    normed, _ = L.add_rms_norm(hidden, residual, params["final_norm"],
                               cfg.norm_eps)
    logits = L.unembed(normed[:, 0], params["lm_head"])
    return logits, {"k": ks, "v": vs}


def decode_step_paged(params, cfg: ModelConfig, pool, page_table, token,
                      pos, *, seq_shard_axis=None, write_mask=None,
                      kv_len=None):
    """One decode step over the paged KV pool.

    pool: ``{"k","v": [L, num_pages, page, Hkv, dh]}`` global block pool;
    page_table: ``[B, pages_per_slot]`` int32 (physical page of logical page
    ``j`` for slot ``b``; unallocated tail entries point at the engine's
    trap page). token/pos as in ``decode_step``. ``write_mask`` (``[B]``
    bool, full slot batch) routes masked-out rows' K/V writes to the trap
    page instead of their table page — the speculative-decoding verify
    program uses it so rejected draft positions never touch the pool; the
    logits math is untouched (a masked row's output is discarded by the
    caller). ``kv_len`` (``[B]``, full slot batch, default ``pos + 1``)
    is the rows each slot's attention reads: the engine passes 0 for a
    slot that holds no request, so the kernel walks none of its pages
    (its row then attends to nothing and reads zeros).

    The new token's K/V scatter goes through the table —
    ``(page_table[b, pos//page], pos % page)`` — and attention gathers
    blocks through the same table (``ops.paged_flash_decode_attention``),
    so the math is bit-identical to ``decode_step`` over the contiguous
    cache the table describes. The pool rides in the scan carry exactly
    like the contiguous cache (in-place aliased carry updates).

    Under an active serving TP plan (``sharding.tp``, traced inside the
    engine's ``shard_map``), the slot batch splits over ``data``: each
    data shard embeds/attends/samples only its own rows, but the pool
    is replicated over ``data`` (radix-shared pages and swap-out reads
    need every row addressable), so the freshly-computed K/V rows are
    all-gathered across ``data`` before the full-batch pool scatter.
    The incoming ``page_table`` is ``data``-sharded (local rows drive
    the attention gather); write indices come from the gathered full
    table. With no plan every ``tp.*`` call is the identity."""
    from repro.kernels import ops
    if seq_shard_axis is not None:
        raise NotImplementedError(
            "sequence-sharded decode uses the contiguous split-KV path")
    hidden = L.embed_tokens(params["embed"], token[:, None]) \
        .astype(cfg.jnp_dtype)                                  # [B,1,D]
    page = pool["k"].shape[2]
    n_pt = page_table.shape[1]
    b_idx = jnp.arange(token.shape[0])
    pt_all = tp.gather_data(page_table)     # full table for write indices
    pidx = jnp.clip(pos // page, 0, n_pt - 1)
    phys = pt_all[b_idx, pidx]              # [B] physical page being written
    if write_mask is not None:
        # rejected speculative positions write to the trap page: the pool
        # never sees their K/V rows, at zero extra cost (page 0 absorbs
        # masked writes by construction)
        phys = jnp.where(write_mask, phys, 0)
    off = pos % page
    hidden = tp.data_shard(hidden)          # this shard's slot rows
    pos_q = tp.data_shard(pos)
    residual = jnp.zeros_like(hidden)
    kv_len = pos_q + 1 if kv_len is None else tp.data_shard(kv_len)

    def body(carry, layer_in):
        p_layer, li = layer_in
        hidden, residual, ks, vs = carry
        # a layer's pages as [P, page, Hkv * dh] rows, contiguous across
        # heads: the row write and the kernel's page DMAs share that layout
        k_l = lax.dynamic_index_in_dim(ks, li, 0, keepdims=False)
        v_l = lax.dynamic_index_in_dim(vs, li, 0, keepdims=False)
        page_shape = k_l.shape
        k_l = k_l.reshape(*page_shape[:2], -1)
        v_l = v_l.reshape(*page_shape[:2], -1)
        normed, residual = L.add_rms_norm(hidden, residual,
                                          p_layer["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = L.qkv_proj(p_layer["attn"], normed, cfg)
        q = L.rope(q, pos_q[:, None], cfg.rope_theta)
        k_new = L.rope(k_new, pos_q[:, None], cfg.rope_theta)
        k_w = tp.gather_data(k_new[:, 0])   # full-batch rows for the pool
        v_w = tp.gather_data(v_new[:, 0])
        k_l = k_l.at[phys, off].set(
            k_w.reshape(k_w.shape[0], -1).astype(k_l.dtype)) \
            .reshape(page_shape)
        v_l = v_l.at[phys, off].set(
            v_w.reshape(v_w.shape[0], -1).astype(v_l.dtype)) \
            .reshape(page_shape)
        ks = lax.dynamic_update_index_in_dim(ks, k_l, li, 0)
        vs = lax.dynamic_update_index_in_dim(vs, v_l, li, 0)
        o = ops.paged_flash_decode_attention(q[:, 0], k_l, v_l, page_table,
                                             kv_len=kv_len)
        attn_out = L.out_proj(p_layer["attn"], o[:, None], o.dtype)
        normed, residual = L.add_rms_norm(attn_out, residual,
                                          p_layer["mlp_norm"], cfg.norm_eps)
        hidden = L.mlp_block(p_layer["mlp"], normed)
        return (hidden, residual, ks, vs), None

    (hidden, residual, ks, vs), _ = lax.scan(
        body, (hidden, residual, pool["k"], pool["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    normed, _ = L.add_rms_norm(hidden, residual, params["final_norm"],
                               cfg.norm_eps)
    logits = L.unembed(normed[:, 0], params["lm_head"])
    return logits, {"k": ks, "v": vs}


def _cached_attention(q, k_cache, v_cache, kv_len, cfg: ModelConfig,
                      seq_shard_axis):
    """Decode attention over the cache; seq-sharded split-KV when mapped."""
    from repro.kernels import ops
    if seq_shard_axis is None:
        return ops.flash_decode_attention(q, k_cache, v_cache, kv_len=kv_len)
    idx = lax.axis_index(seq_shard_axis)
    shard = k_cache.shape[1]
    local_len = jnp.clip(kv_len - idx * shard, 0, shard)
    o_part, lse = ops.flash_decode_attention(
        q, k_cache, v_cache, kv_len=local_len, return_lse=True)
    o_part = jnp.where(jnp.isneginf(lse)[..., None], 0.0,
                       o_part.astype(jnp.float32))
    m = lax.pmax(lse, seq_shard_axis)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - m_safe))
    num = lax.psum(w[..., None] * o_part, seq_shard_axis)
    den = lax.psum(w, seq_shard_axis)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
