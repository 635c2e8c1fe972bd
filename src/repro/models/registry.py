"""Architecture registry — one uniform API over all model families.

Every family module exposes:
    init(cfg, key) -> (params, logical_axes)
    loss_fn(params, cfg, batch) -> scalar        (training)
    prefill(params, cfg, prompt) -> (logits, cache)
    decode_step(params, cfg, cache, token, pos, *, seq_shard_axis) -> ...
    cache_spec(cfg, batch, seq) -> (ShapeDtypeStruct tree, logical axes)

``batch_spec``/``prompt_spec`` build the ShapeDtypeStruct stand-ins for the
dry-run (no allocation) and the synthetic-data pipeline shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe, recurrentgemma, seamless, transformer, xlstm

_FAMILY = {
    "dense": transformer,
    "moe": moe,
    "xlstm": xlstm,
    "hybrid": recurrentgemma,
    "encdec": seamless,
}


def module_for(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def init(cfg: ModelConfig, key):
    return module_for(cfg).init(cfg, key)


def loss_fn(params, cfg: ModelConfig, batch):
    return module_for(cfg).loss_fn(params, cfg, batch)


def prefill(params, cfg: ModelConfig, prompt, *, cache_len=None,
            length=None):
    return module_for(cfg).prefill(params, cfg, prompt,
                                   cache_len=cache_len, length=length)


def decode_step(params, cfg: ModelConfig, cache, token, pos, *,
                seq_shard_axis=None):
    return module_for(cfg).decode_step(params, cfg, cache, token, pos,
                                       seq_shard_axis=seq_shard_axis)


def cache_spec(cfg: ModelConfig, batch: int, seq: int):
    return module_for(cfg).cache_spec(cfg, batch, seq)


def init_cache(cfg: ModelConfig, batch: int, seq: int):
    return module_for(cfg).init_cache(cfg, batch, seq)


def pad_prefill_ok(cfg: ModelConfig) -> bool:
    """True when this family's prefill is exact under right-padding — the
    serving engine buckets prompt lengths to powers of two only then."""
    return bool(getattr(module_for(cfg), "PAD_PREFILL", False))


def paged_ok(cfg: ModelConfig) -> bool:
    """True when this arch can serve from a paged KV pool: the family
    declares ``PAGED_OK`` (positional K/V, slot-independent decode, exact
    recompute preemption) AND the arch has no rolling window (a windowed
    cache is already bounded and its pos%window layout does not page)."""
    return (bool(getattr(module_for(cfg), "PAGED_OK", False))
            and not cfg.window)


def paged_cache_spec(cfg: ModelConfig, num_pages: int, page_size: int):
    return module_for(cfg).paged_cache_spec(cfg, num_pages, page_size)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    return module_for(cfg).init_paged_cache(cfg, num_pages, page_size)


def decode_step_paged(params, cfg: ModelConfig, pool, page_table, token,
                      pos, *, seq_shard_axis=None, write_mask=None,
                      kv_len=None):
    return module_for(cfg).decode_step_paged(
        params, cfg, pool, page_table, token, pos,
        seq_shard_axis=seq_shard_axis, write_mask=write_mask, kv_len=kv_len)


def decode_cached(params, cfg: ModelConfig, cache, token, pos, *,
                  page_table=None, seq_shard_axis=None, write_mask=None,
                  kv_len=None):
    """One decode step against either cache layout — the single decode
    surface the serving ``CacheManager`` implementations dispatch through:
    ``page_table=None`` selects the contiguous per-slot pool,
    a ``[B, pages_per_slot]`` table selects the paged block pool.
    ``write_mask`` (paged only) routes masked rows' K/V writes to the trap
    page — the speculative-decoding verify path. ``kv_len`` (paged only,
    default ``pos + 1``) is the rows each slot's attention reads."""
    if page_table is None:
        if write_mask is not None or kv_len is not None:
            raise ValueError("write_mask and kv_len require the paged "
                             "cache layout (the contiguous pool has no "
                             "trap page)")
        return decode_step(params, cfg, cache, token, pos,
                           seq_shard_axis=seq_shard_axis)
    return decode_step_paged(params, cfg, cache, page_table, token, pos,
                             seq_shard_axis=seq_shard_axis,
                             write_mask=write_mask, kv_len=kv_len)


def write_cached(cfg: ModelConfig, cache, new, *, slot=None, pages=None,
                 max_seq=None, page_size=None):
    """Scatter one request's prefill cache into either layout — the single
    write surface behind ``CacheManager.write``: pass ``slot`` (+
    ``max_seq``) for the contiguous pool or ``pages`` (+ ``page_size``)
    for the paged pool. Exactly one of ``slot``/``pages`` must be given."""
    if (slot is None) == (pages is None):
        raise ValueError("write_cached wants exactly one of slot= / pages=")
    if pages is not None:
        return write_pages(cfg, cache, new, pages, page_size)
    return write_slot(cfg, cache, new, slot, max_seq)


def write_slot(cfg: ModelConfig, pool, new, slot, max_seq: int):
    """Scatter one request's prefill cache (batch=1) into pool slot ``slot``.

    The batch/seq axes differ per family (xLSTM stacks states as
    [periods, stack, batch, ...]; Griffin mixes KV and recurrent leaves), so
    the scatter is driven by the logical axes from ``cache_spec`` — each
    leaf is written along its "batch" axis and clipped along "kv_seq" to
    the pool's sequence capacity. ``slot`` may be a traced scalar, so one
    jitted admission function serves every slot. (The historical engine
    hardcoded axis 1, silently corrupting xLSTM/Griffin recurrent state on
    slot scatter.)"""
    _, axes = cache_spec(cfg, 1, max_seq)
    is_ax = lambda x: isinstance(x, tuple)
    pool_leaves, treedef = jax.tree.flatten(pool)
    new_leaves = jax.tree.leaves(new)
    ax_leaves = jax.tree.leaves(axes, is_leaf=is_ax)
    out = []
    for p, n, ax in zip(pool_leaves, new_leaves, ax_leaves):
        ba = ax.index("batch")
        if "kv_seq" in ax:
            sa = ax.index("kv_seq")
            cap, s = p.shape[sa], n.shape[sa]
            if s > cap:  # rolling-window prefill keeps the last `cap`
                n = jax.lax.slice_in_dim(n, s - cap, s, axis=sa)
        starts = [0] * p.ndim
        starts[ba] = jnp.asarray(slot, jnp.int32)
        out.append(jax.lax.dynamic_update_slice(
            p, n.astype(p.dtype), tuple(starts)))
    return jax.tree.unflatten(treedef, out)


def read_pages(cfg: ModelConfig, pool, pages, page_size: int):
    """Gather whole pages out of the paged pool back into prefill layout
    (``[..., batch=1, n*page_size, ...]`` per leaf) — the exact inverse of
    ``write_pages``. The serving engine's swap-preemption path reads a
    victim's pages to host with this and later writes the same bytes back
    through ``write_pages``, so a preempted request's logical cache is
    restored bit-for-bit."""
    _, axes = cache_spec(cfg, 1, page_size)
    is_ax = lambda x: isinstance(x, tuple)
    pool_leaves, treedef = jax.tree.flatten(pool)
    ax_leaves = jax.tree.leaves(axes, is_leaf=is_ax)
    out = []
    for p, ax in zip(pool_leaves, ax_leaves):
        ba, sa = ax.index("batch"), ax.index("kv_seq")
        if sa != ba + 1:
            raise ValueError(f"paged layout needs adjacent (batch, kv_seq) "
                             f"axes, got {ax}")
        g = jnp.moveaxis(jnp.moveaxis(p, ba, 0)[pages], 0, ba)
        g = g.reshape(g.shape[:ba] + (-1,) + g.shape[ba + 2:])
        out.append(jnp.expand_dims(g, ba))
    return jax.tree.unflatten(treedef, out)


def write_pages(cfg: ModelConfig, pool, new, pages, page_size: int):
    """Scatter one request's prefill cache (batch=1) into whole pool pages.

    ``pool`` is the paged block pool from ``init_paged_cache`` (the
    contiguous cache's adjacent (batch, kv_seq) axes become the global
    (pages, page) axes); ``pages`` is a ``[n]`` int32 vector of physical
    page destinations for the prompt's logical pages 0..n-1.  Like
    ``write_slot``, the scatter is axes-driven off ``cache_spec``'s logical
    axes, so it holds for any paged family layout.  ``pages`` may be a
    traced vector: one jitted admission function serves every allocation
    pattern of a given prompt bucket.  Entries in ``pages`` may repeat the
    engine's trap page (bucket tail past the allocated prefix); duplicate
    destinations only ever carry masked pad garbage."""
    _, axes = cache_spec(cfg, 1, page_size)
    is_ax = lambda x: isinstance(x, tuple)
    pool_leaves, treedef = jax.tree.flatten(pool)
    new_leaves = jax.tree.leaves(new)
    ax_leaves = jax.tree.leaves(axes, is_leaf=is_ax)
    n_pages = pages.shape[0]
    target = n_pages * page_size
    out = []
    for p, n, ax in zip(pool_leaves, new_leaves, ax_leaves):
        ba, sa = ax.index("batch"), ax.index("kv_seq")
        if sa != ba + 1:
            raise ValueError(f"paged layout needs adjacent (batch, kv_seq) "
                             f"axes, got {ax}")
        n = jnp.squeeze(n, axis=ba)              # batch=1 -> seq at axis ba
        s = n.shape[ba]
        if s < target:
            pad = [(0, 0)] * n.ndim
            pad[ba] = (0, target - s)
            n = jnp.pad(n, pad)
        elif s > target:
            n = jax.lax.slice_in_dim(n, 0, target, axis=ba)
        n = n.reshape(n.shape[:ba] + (n_pages, page_size) + n.shape[ba + 1:])
        pm = jnp.moveaxis(p, ba, 0)              # pages axis leading
        nm = jnp.moveaxis(n, ba, 0)
        pm = pm.at[pages].set(nm.astype(p.dtype))
        out.append(jnp.moveaxis(pm, 0, ba))
    return jax.tree.unflatten(treedef, out)


def prefix_cache_ok(cfg: ModelConfig) -> bool:
    """True when this arch can reuse radix-cached prefix pages: it must
    serve paged (``paged_ok``), take token-id prompts (frame frontends
    have no hashable token chunks), and implement ``prefill_suffix``."""
    return (paged_ok(cfg) and cfg.frontend != "frames"
            and hasattr(module_for(cfg), "prefill_suffix"))


def prefill_suffix(params, cfg: ModelConfig, tokens, prefix, *,
                   prefix_len, length=None):
    """Prefill only a prompt's suffix against gathered prefix KV rows —
    the radix-prefix-hit admission path. See the family module."""
    return module_for(cfg).prefill_suffix(params, cfg, tokens, prefix,
                                          prefix_len=prefix_len,
                                          length=length)


def copy_pages(cfg: ModelConfig, pool, src, dst, page_size: int):
    """Device-side whole-page duplication (copy-on-write): copy physical
    page ``src`` into ``dst`` on every cache leaf. Axes-driven like
    ``write_pages`` — the pool's pages axis sits where the contiguous
    spec's batch axis was."""
    _, axes = cache_spec(cfg, 1, page_size)
    is_ax = lambda x: isinstance(x, tuple)
    pool_leaves, treedef = jax.tree.flatten(pool)
    ax_leaves = jax.tree.leaves(axes, is_leaf=is_ax)
    out = []
    for p, ax in zip(pool_leaves, ax_leaves):
        ba = ax.index("batch")
        pm = jnp.moveaxis(p, ba, 0)
        pm = pm.at[dst].set(pm[src])
        out.append(jnp.moveaxis(pm, 0, ba))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStructs; the dry-run's only "data")
# --------------------------------------------------------------------------

def batch_spec(cfg: ModelConfig, batch: int, seq: int):
    """Training batch ShapeDtypeStructs + logical shard axes."""
    i32 = jnp.int32
    if cfg.frontend == "frames":
        spec = {"frames": jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                               cfg.jnp_dtype),
                "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
                "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
        axes = {"frames": ("batch", None, None), "tokens": ("batch", None),
                "labels": ("batch", None)}
    else:
        spec = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
                "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
        axes = {"tokens": ("batch", None), "labels": ("batch", None)}
    return spec, axes


def prompt_spec(cfg: ModelConfig, batch: int, seq: int):
    """Prefill prompt ShapeDtypeStructs + logical axes."""
    if cfg.frontend == "frames":
        return (jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                     cfg.jnp_dtype),
                ("batch", None, None))
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32), ("batch", None)


def make_batch(cfg: ModelConfig, batch: int, seq: int, key):
    """Synthetic concrete batch (smoke tests / examples)."""
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab)
    out = {"tokens": tokens,
           "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.frontend == "frames":
        out["frames"] = jax.random.normal(k2, (batch, seq, cfg.d_model),
                                          cfg.jnp_dtype)
    return out


def train_batch_arg(cfg: ModelConfig, batch):
    """The positional arg loss_fn expects (tokens-only families ignore
    frames)."""
    return batch
