"""Drafting layer of the speculative-decoding subsystem.

A ``Drafter`` proposes ``k`` candidate next tokens per slot per decode
step; the engine's fused verify program scores all ``k + 1`` positions
with the target model in ONE donated dispatch, commits the longest
accepted prefix on-device, and routes every rejected position's KV
write to the paged pool's trap page. Two implementations:

``NGramDrafter`` — prompt-lookup self-drafting (no extra model): the
request's own prompt + emitted history is searched for the most recent
recurrence of its trailing n-gram, and the ``k`` tokens that followed
it are proposed. Stateless — nothing to snapshot, restore, or roll
back; host-side and exact under preemption by construction.

``DraftModelDrafter`` — a small dense draft model (e.g. qwen2-0.5b
drafting for qwen3-8b) with its own contiguous ``slots x max_seq`` KV
cache. Each step it runs ``k + 1`` greedy decode steps in one jitted,
donated scan (the extra step writes the last draft's KV row so an
all-accept verify leaves the draft cache complete), feeding the
target's committed carry token first, so its state mirrors the target
stream exactly on every accepted position. Rejection rollback is free:
stale rows past the committed position are masked by ``kv_len`` and
overwritten by the next scan before they can be read. Swap preemption
snapshots the victim's draft rows to host and restores them
byte-for-byte on re-admission; crash recovery resets the cache and
replays survivors from their snapshots.

Both proposers return target-vocab token ids; a wrong proposal is
never wrong *output* — the verify program only commits draft positions
whose token equals the target model's own argmax, so greedy spec
streams stay bitwise identical to target-only decoding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.serving.spec.config import SpecConfig


class Drafter:
    """Proposal + state-management surface the engine drives.

    ``propose`` runs once per spec decode step and returns ``[slots, k]``
    candidate token ids (numpy or device array — the engine ships either
    to the verify program). The state hooks are no-ops for stateless
    drafters; ``stateful`` gates the engine's snapshot/restore plumbing
    so swap payloads don't grow a ``None`` tree per request.
    """

    stateful = False

    def propose(self, slots, token, pos):
        """``[slots, k]`` i32 proposals for the current decode carry."""
        raise NotImplementedError

    def prefill(self, slot, tokens) -> None:
        """Admission hook: process ``tokens`` (the full prompt, generated
        prefix included on recompute re-admission) into ``slot``'s
        drafter state."""

    def snapshot_slot(self, slot):
        """Host copy of ``slot``'s drafter state (swap-out), or None."""
        return None

    def restore_slot(self, slot, saved) -> None:
        """Write a ``snapshot_slot`` payload back (swap-in)."""

    def reset(self) -> None:
        """Drop all drafter state (device-fault crash recovery)."""


class NGramDrafter(Drafter):
    """Prompt-lookup self-drafting: propose the continuation of the most
    recent earlier occurrence of the stream's trailing n-gram.

    Matching backs off from ``ngram`` down to 1 token; no recurrence
    anywhere proposes zeros (a valid token id — the verify program just
    rejects it). Host-side and stateless: exact under preemption, abort,
    and crash recovery with nothing to roll back.
    """

    def __init__(self, k: int, ngram: int):
        """``k`` tokens proposed per step, matching up to ``ngram``."""
        self.k, self.ngram = k, ngram

    def propose(self, slots, token, pos):
        """Per-slot lookup over prompt + emitted history (host arrays)."""
        out = np.zeros((len(slots), self.k), np.int32)
        for i, s in enumerate(slots):
            if s.req is None or not s.dactive:
                continue
            prompt = np.asarray(s.req.prompt)
            ctx = np.concatenate(
                [prompt, np.asarray(s.req.out_tokens, prompt.dtype)])
            out[i] = self._lookup(ctx.astype(np.int64))
        return out

    def _lookup(self, ctx: np.ndarray) -> np.ndarray:
        """Continuation of the last recurrence of the trailing n-gram."""
        n_ctx = len(ctx)
        out = np.zeros((self.k,), np.int32)
        for n in range(min(self.ngram, n_ctx - 1), 0, -1):
            tail = ctx[n_ctx - n:]
            # every length-n window that still has a continuation token
            win = np.lib.stride_tricks.sliding_window_view(
                ctx[:n_ctx - 1], n)
            hits = np.flatnonzero((win == tail).all(axis=1))
            if hits.size:
                j = int(hits[-1]) + n
                cand = ctx[j:j + self.k]
                out[:len(cand)] = cand
                break
        return out


class DraftModelDrafter(Drafter):
    """Small-draft-model drafting with a contiguous per-slot KV mirror.

    The draft cache keeps the engine's carry invariant — positions
    ``0..pos-1`` processed, the carry token's row unwritten at ``pos`` —
    and both tokenizer and vocab ids are shared with the target (the
    config validates ``draft_vocab >= target_vocab``). All device work
    is jitted and donated; the proposal scan costs one extra small-model
    dispatch per step and zero extra host readbacks (drafts stay on
    device for the verify program).
    """

    stateful = True

    def __init__(self, params, cfg, k: int, slots: int, max_seq: int,
                 dev=None, dispatch=None):
        """``dev`` places arrays for the engine's mesh (identity off);
        ``dispatch(fn, *args)`` runs each jitted program (the engine's,
        so a draft program that cannot run is not taken for a request's
        fault; a plain call by default)."""
        self.cfg, self.k = cfg, k
        self.slots, self.max_seq = slots, max_seq
        self._dev = dev if dev is not None else (lambda x: x)
        self._run = dispatch if dispatch is not None \
            else (lambda fn, *args: fn(*args))
        self.params = jax.tree.map(self._dev, params)
        _, self._axes = registry.cache_spec(cfg, slots, max_seq)
        self.cache = self._fresh_cache()
        self._scan_fn = self._jit_scan()
        self._prefill_fn = self._jit_prefill()
        self._restore_fn = self._jit_restore()

    def _fresh_cache(self):
        """Zeroed draft KV pool, placed wherever the engine's mesh is."""
        cache, _ = registry.init_cache(self.cfg, self.slots, self.max_seq)
        return jax.tree.map(self._dev, cache)

    def _jit_scan(self):
        """``k + 1`` greedy draft decode steps fused into one donated jit.

        Step ``j`` writes its input's KV row at ``pos + j`` and produces
        draft ``j + 1``; the final step's output is discarded but its
        write covers the all-accept case, so the mirror is complete
        however many drafts the verify program commits. Stale rows past
        the committed position are dead: ``kv_len`` masks them and the
        next scan overwrites them before any read — rollback is free.
        """
        cfg, k, vocab = self.cfg, self.k, self.cfg.vocab

        def scan(params, cache, token, pos):
            x, drafts = token, []
            for j in range(k + 1):
                logits, cache = registry.decode_step(params, cfg, cache,
                                                     x, pos + j)
                x = jnp.argmax(logits[:, :vocab], axis=-1) \
                    .astype(jnp.int32)
                if j < k:
                    drafts.append(x)
            return cache, jnp.stack(drafts, axis=1)

        return jax.jit(scan, donate_argnums=(1,))

    def _jit_prefill(self):
        """Fused draft prefill + slot scatter, keyed by the pow2 bucket
        (compile count mirrors the target's bucketed admission; the
        engine's ``prefill_compiles`` stat never sees these)."""
        cfg, max_seq = self.cfg, self.max_seq

        def prefill(params, cache, prompt, length, slot):
            _, kv = registry.prefill(params, cfg, prompt[None],
                                     length=length)
            return registry.write_slot(cfg, cache, kv, slot, max_seq)

        return jax.jit(prefill, donate_argnums=(1,))

    def _jit_restore(self):
        """Jitted swap-in scatter of a snapshot back into its slot."""
        cfg, max_seq = self.cfg, self.max_seq

        def restore(cache, saved, slot):
            return registry.write_slot(cfg, cache, saved, slot, max_seq)

        return jax.jit(restore, donate_argnums=(0,))

    def propose(self, slots, token, pos):
        """One donated scan dispatch; drafts stay on device."""
        self.cache, drafts = self._run(self._scan_fn, self.params,
                                       self.cache, token, pos)
        return drafts

    def prefill(self, slot, tokens) -> None:
        """Process the full prompt into ``slot``'s draft cache (pow2
        bucketed + right-padded; exact under padding — dense family)."""
        tokens = np.asarray(tokens)
        n = len(tokens)
        b = 1
        while b < n:
            b *= 2
        b = min(b, self.max_seq)
        if b > n:
            tokens = np.concatenate(
                [tokens, np.zeros((b - n,), tokens.dtype)])
        self.cache = self._run(self._prefill_fn, self.params, self.cache,
                               jnp.asarray(tokens), jnp.int32(n),
                               jnp.int32(slot))

    def snapshot_slot(self, slot):
        """Host copy of ``slot``'s rows on every cache leaf (swap-out)."""
        def cut(leaf, ax):
            idx = [slice(None)] * leaf.ndim
            idx[ax.index("batch")] = slice(slot, slot + 1)
            return np.asarray(leaf[tuple(idx)])

        is_ax = lambda x: isinstance(x, tuple)
        leaves, treedef = jax.tree.flatten(self.cache)
        axes = jax.tree.leaves(self._axes, is_leaf=is_ax)
        return jax.tree.unflatten(
            treedef, [cut(p, ax) for p, ax in zip(leaves, axes)])

    def restore_slot(self, slot, saved) -> None:
        """Byte-for-byte swap-in of a ``snapshot_slot`` payload."""
        self.cache = self._run(self._restore_fn, self.cache,
                               jax.tree.map(jnp.asarray, saved),
                               jnp.int32(slot))

    def reset(self) -> None:
        """Fresh zeroed cache (same shapes/placement: no retrace)."""
        self.cache = self._fresh_cache()


def make_drafter(spec: SpecConfig, cfg, slots: int, max_seq: int,
                 dev=None, dispatch=None) -> Drafter:
    """Resolve a ``SpecConfig`` into a ready drafter for this engine.

    Validates the draft model against the target: token frontend, exact
    right-padded prefill (the drafter buckets prompts like the engine),
    and a vocab covering every target token id (proposals and the
    target's committed carries cross between the two models verbatim).
    """
    if spec.drafter == "ngram":
        return NGramDrafter(spec.k, spec.ngram)
    dcfg = spec.draft_cfg
    if getattr(dcfg, "frontend", "tokens") == "frames":
        raise ValueError("draft models take token prompts; a frames "
                         "frontend cannot draft")
    if not registry.pad_prefill_ok(dcfg):
        raise ValueError(
            f"draft family {dcfg.family!r} has no exact right-padded "
            "prefill; use a dense draft model (or drafter='ngram')")
    if dcfg.vocab < cfg.vocab:
        raise ValueError(
            f"draft vocab {dcfg.vocab} cannot cover target vocab "
            f"{cfg.vocab}: proposals are target token ids")
    return DraftModelDrafter(spec.draft_params, dcfg, spec.k, slots,
                             max_seq, dev=dev, dispatch=dispatch)
