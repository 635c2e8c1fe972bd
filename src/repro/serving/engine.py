"""Device-resident continuous-batching serving engine.

A fixed pool of decode slots; requests join as slots free up (continuous
batching à la SGLang/vLLM). The engine is the execution core of a layered
serving API:

* **Sampling** (``repro.serving.sampling.SamplingParams``) — greedy /
  temperature / top-k / top-p with a per-request seed. The draw is fused
  into the donated decode step: a per-slot categorical draw keyed by a
  ``jax.random`` key buffer living in the donated carry, so non-greedy
  decode still costs ONE batched host readback per step and token *t* of a
  request is a pure function of ``(seed, t)`` — bit-reproducible across
  restarts, cache managers, and preemption.
* **Scheduling** (``repro.serving.scheduler``) — admission order is a
  pluggable ``Scheduler`` (FCFS default — bit-identical to the historical
  deque — plus priority and shortest-job-first); victim choice and
  eviction semantics are a ``PreemptionPolicy`` (youngest-victim swap /
  recompute).
* **Cache management** (``repro.serving.cache_manager``) — the contiguous
  ``slots x max_seq`` pool and the paged ``PagePool`` + page-table layout
  sit behind one ``CacheManager`` ``alloc/write/grow/evict/restore``
  surface; ``CacheConfig(paged=None)`` auto-selects per family and
  ``num_pages`` below full subscription oversubscribes (admission waits
  for pages, decode growth preempts when the pool runs dry).
* **Facade** (``repro.serving.api.LLMEngine``) — ``generate()`` /
  ``stream()`` over this engine for callers who don't want to manage
  ``Request`` objects.

The decode hot path never leaves the device: one donated jitted program
per step (model decode + fused sampling + stop conditions + slot masking,
``donate_argnums`` on the KV/state pool and the token/pos/active/emitted/
key buffers), one batched ``(token-or-minus-one, done)`` host readback per
step with step *k*'s readback overlapped against step *k+1*'s dispatch,
and bucketed jitted prefill admission (pow2 prompt buckets for
``PAD_PREFILL`` families, exact length for stateful ones).

Under a ``jax.profiler`` trace the host side shows up as spans on the
device's clock: ``engine.step`` around each step, and inside it
``engine.admit`` (per admission attempt, ``rid``) with its
``engine.first_token`` read, ``engine.ensure_pages``, ``engine.dispatch``
and ``engine.readback``. With no profiler running each costs a
microsecond or two.

Greedy FCFS token streams are bit-identical to the historical host-driven
engine (``repro.serving.reference.ReferenceEngine``) — paged or not,
preempted or not; asserted end-to-end in ``tests/test_serving.py`` and by
the CI golden-stream check. The old constructor kwargs (``greedy=``,
``preempt=``, ``paged=``/``page_size=``/``num_pages=``) keep working
through deprecation shims that forward to the new layers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.serving.cache_manager import CacheConfig, make_cache_manager
from repro.serving.chaos import ChaosInjector
from repro.serving.sampling import SamplingParams, sample_tokens
from repro.serving.scheduler import make_preemption, make_scheduler
from repro.serving.spec import make_drafter
from repro.sharding import tp


@contextlib.contextmanager
def _quiet_donation():
    """Donation is a TPU/GPU in-place-update optimization; the CPU backend
    ignores it and warns once per compile. Scoped to the engine's dispatch
    sites so importing this module doesn't mutate the global filter."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class ProgramError(Exception):
    """A jitted program raised before it had ever completed a dispatch.

    That is a failure of the program itself on this backend — it does not
    compile or launch (a Mosaic or XLA error) — and no request caused it.
    Deliberately not a ``RuntimeError``: the per-request failure isolation
    below catches those, and would turn a program that cannot run into
    ``failed`` outputs and a clean exit. The original error is the
    ``__cause__``."""


@dataclasses.dataclass
class Request:
    """One generation request: prompt, budget, sampling, and its stream."""

    rid: int
    prompt: np.ndarray                  # token ids [S] (or frames [S, D])
    max_new_tokens: int = 16
    sampling: Optional[SamplingParams] = None   # None -> engine default
    priority: int = 0                   # consumed by PriorityScheduler
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0               # set by Engine.submit
    t_admit: float = 0.0                # first pop from the queue (admission)
    t_first: float = 0.0                # wall time of the first token (TTFT)
    preemptions: int = 0                # paged engine: times evicted+requeued
    arrival: int = -1                   # submission rank, stamped by submit
    prefix_hit_tokens: int = 0          # prompt tokens served from the radix
                                        # cache instead of prefill
    deadline_s: Optional[float] = None  # wall-clock budget from t_submit;
                                        # expiry finishes as "deadline"
    # lifecycle outcome: None while live, then one of
    # done | aborted | rejected | failed | deadline
    finish_reason: Optional[str] = None
    error: Optional[str] = None         # human-readable failure detail
    accepted_tokens: int = 0            # draft tokens the spec verify
                                        # committed (0 with spec off)
    # swap-preemption payload: (host KV pages, token, pos, emitted,
    # n_pages, drafter snapshot-or-None) — the victim's exact device
    # state, restored verbatim on re-admission
    swap_state: Optional[tuple] = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    # exact host mirror of the device's per-slot decode state — the device
    # stop conditions are deterministic, so the host can track position,
    # emit count, and active-ness without waiting for the (overlapped)
    # readback. The paged allocator predicts each step's write page from
    # ``dpos``; the drain heuristic reads ``dactive``.
    dpos: int = 0                       # device pos (next write position)
    demitted: int = 0                   # device emitted count
    dactive: bool = False               # device active flag


class Engine:
    """Device-resident continuous-batching core: one donated jitted program
    and one batched host readback per decode step."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_seq: int = 512,
                 sampling: Optional[SamplingParams] = None,
                 scheduler=None, preemption=None, cache_manager=None,
                 chaos=None, mesh=None, spec=None,
                 greedy: Optional[bool] = None,
                 preempt: Optional[str] = None,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None):
        """``sampling`` is the default ``SamplingParams`` for requests that
        don't carry their own (greedy when omitted). ``scheduler`` /
        ``preemption`` / ``cache_manager`` take a policy name, a config,
        or a ready instance — see ``repro.serving.scheduler`` and
        ``repro.serving.cache_manager``. ``chaos`` takes a
        ``serving.chaos.ChaosInjector`` (or a plain ``reliability.Fault``
        list) whose scheduled faults are injected into the decode loop.
        ``mesh`` takes a ``(data, model)`` ``jax.sharding.Mesh`` (see
        ``launch/mesh.py``): the donated programs run under ``shard_map``
        with weights, the paged KV pool, and the slot batch sharded per
        the plan ``repro.sharding.tp`` resolves from the logical-axis
        rules — token streams stay bit-identical to the single-device
        engine (all collectives are all-gathers). ``spec`` takes a
        ``repro.serving.spec.SpecConfig``: the drafter proposes ``k``
        tokens per step and the donated step verifies all ``k + 1``
        positions at once, committing the longest accepted prefix
        on-device (rejected positions write to the trap page) — still
        one batched host readback per step, and greedy streams bitwise
        identical to target-only decoding. Like
        ``CacheConfig.prefix_cache`` it is silently inert where it
        cannot run (contiguous cache managers, frame frontends).

        ``greedy=``, ``preempt=``, and ``paged=``/``page_size=``/
        ``num_pages=`` are the pre-layered kwargs, kept as deprecation
        shims that forward to the new layers."""
        if greedy is not None:
            warnings.warn(
                "Engine(greedy=...) is deprecated; pass "
                "sampling=SamplingParams(...) instead", DeprecationWarning,
                stacklevel=2)
            if sampling is None:
                sampling = SamplingParams() if greedy \
                    else SamplingParams(temperature=1.0)
        if preempt is not None:
            warnings.warn(
                "Engine(preempt=...) is deprecated; pass preemption= a "
                "repro.serving.scheduler.PreemptionPolicy (or its name)",
                DeprecationWarning, stacklevel=2)
            if preemption is None:
                preemption = preempt
        if paged is not None or page_size is not None \
                or num_pages is not None:
            warnings.warn(
                "Engine(paged=/page_size=/num_pages=) is deprecated; pass "
                "cache_manager=CacheConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            if cache_manager is None:
                cache_manager = CacheConfig(paged=paged,
                                            page_size=page_size or 16,
                                            num_pages=num_pages)
        self.params, self.cfg = params, cfg
        self.n_slots, self.max_seq = slots, max_seq
        # (callable, argument signature) of every compiled program that
        # has completed a dispatch (``_dispatch``)
        self._proven: set = set()
        self.slots = [_Slot() for _ in range(slots)]
        self.default_sampling = sampling if sampling is not None \
            else SamplingParams()
        self.scheduler = make_scheduler(scheduler)
        self.preemption = make_preemption(preemption)
        self.preempt_mode = self.preemption.mode
        self.cm = make_cache_manager(cache_manager, cfg, slots, max_seq)
        self.paged = self.cm.paged
        self.page_size = getattr(self.cm, "page_size", None)
        self.num_pages = getattr(self.cm, "num_pages", None)
        self._plan = None
        if mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh serving requires the paged cache manager (the "
                    "contiguous cache keeps the split-KV shard_map path)")
            self._plan = tp.make_plan(cfg, mesh, slots)
            # weights move to the mesh here (gate/up columns permuted per
            # shard when the MLP axis shards); carries and the pool get
            # replicated / heads-sharded placements below so donation
            # round-trips a consistent committed sharding
            self.params = tp.shard_params(params, cfg, self._plan)
            self._pspecs = tp.param_specs(self.params, self._plan)
        self.cache = self._new_cache()
        self.chaos = None
        if chaos is not None:
            self.chaos = chaos if hasattr(chaos, "on_step") \
                else ChaosInjector(chaos)
        self.finished: list[Request] = []
        self.preemptions = 0
        self.recoveries = 0
        self._lifecycle = {"done": 0, "aborted": 0, "rejected": 0,
                           "failed": 0, "deadline": 0}
        self._has_deadlines = False
        self._arrivals = 0
        self._pad_ok = registry.pad_prefill_ok(cfg)
        # device-resident per-slot decode state (+ per-slot sampling
        # parameters and the per-request base PRNG keys — the key buffer
        # rides in the donated carry with the rest)
        self._fresh_carries()
        # the decode step specializes on "has any resident request ever
        # been non-greedy": the all-greedy program is the historical bare
        # argmax; admitting the first sampling request rebuilds it once
        self._greedy_only = self.default_sampling.greedy
        self._step_fn = self._jit_step(self._greedy_only)
        # Admission (prefill + pool scatter + slot state reset) is ONE
        # jitted program keyed by the (padded) prompt shape: bucketed
        # families compile at most log2(max_seq)+1 of them; exact-length
        # families (MoE capacity routing, recurrences, bidirectional
        # encoders) compile per unique length — the historical engine's
        # behavior, minus its eager scatter and host argmax.
        self._admit_fn = self._jit_admit(self._greedy_only)
        # prefill compiles accumulated by admit programs replaced on the
        # greedy->sampling flip (stats() adds the live program's count)
        self._compiles_base = 0
        if self.paged:
            # swap-in restore; compile key = saved page count (<= n_pt)
            self._restore_fn = self._jit_restore()
        # speculative decoding: active only where the paged pool (trap
        # page for rejected writes) and token prompts exist — silently
        # inert elsewhere, mirroring CacheConfig.prefix_cache. ``spec``
        # is the EFFECTIVE config (None when inert); ``spec_config`` the
        # requested one, kept so stats always surface the spec counters.
        self.spec_config = spec
        self.spec = None
        self._drafter = None
        if spec is not None and self.paged and cfg.frontend != "frames":
            self.spec = spec
            self._drafter = make_drafter(spec, cfg, slots, max_seq,
                                         dev=self._dev,
                                         dispatch=self._dispatch)
            # the fused draft-verify step: k+1 sequential inner decode
            # steps in ONE donated program (greedy-only by construction
            # — non-greedy requests are rejected at admission)
            self._spec_step_fn = self._jit_step_spec()
        self._spec_slot_steps = 0   # active-slot spec dispatches
        self._spec_emitted = 0      # tokens committed by spec steps
        self._prefix_cache = self.paged and self.cm.prefix_cache \
            and self._pad_ok
        if self._prefix_cache:
            # radix-hit admission: gather prefix pages + prefill the
            # suffix only; compile key = the suffix bucket shape
            self._admit_suffix_fn = self._jit_admit_suffix(
                self._greedy_only)
            # whole-page device copy for copy-on-write
            self._cow_fn = self._jit_cow()
        # (emit arrays, request snapshot) of the last dispatched step, not
        # yet read back — drained after the NEXT dispatch (overlap)
        self._pending = None
        self._steps = 0
        self._readbacks = 0
        self._suffix_shapes: set[int] = set()

    def _dispatch(self, fn, *args):
        """Run one jitted program. A ``RuntimeError`` from a compiled
        program that has already completed a dispatch is a per-request
        fault, left to the caller's isolation; from one that never has,
        it is raised as ``ProgramError``. One callable compiles a program
        per argument signature (each prefill bucket is its own), so a
        program is keyed by the callable and its arguments' shapes, dtypes
        and shardings."""
        key = (fn, tuple(
            (np.shape(a), getattr(a, "dtype", type(a)),
             getattr(a, "sharding", None))
            for a in jax.tree.leaves(args)))
        try:
            with _quiet_donation():
                out = fn(*args)
        except RuntimeError as e:
            if key in self._proven:
                raise
            raise ProgramError(
                f"{getattr(fn, '__name__', 'program')} failed on its first "
                f"dispatch: {e}") from e
        self._proven.add(key)
        return out

    # -- device placement (mesh) ---------------------------------------------

    def _dev(self, x):
        """Replicate a carry buffer on the mesh (identity off-mesh)."""
        return x if self._plan is None else tp.replicate(x, self._plan)

    def _new_cache(self):
        """A fresh KV pool: on the mesh, built straight into its sharding
        (kv_heads over ``model`` when the plan shards heads), so no
        device ever holds the whole pool; off-mesh, the cache manager's
        zero-fill on the default device."""
        return self.cm.init() if self._plan is None \
            else tp.make_cache(self.cm.init, self._plan)

    def _fresh_carries(self) -> None:
        """(Re)build the nine per-slot carry buffers as zeros — shared by
        ``__init__`` and the device-fault recovery (same shapes, so the
        step program never retraces)."""
        slots = self.n_slots
        self._token = self._dev(jnp.zeros((slots,), jnp.int32))
        self._pos = self._dev(jnp.zeros((slots,), jnp.int32))
        self._active = self._dev(jnp.zeros((slots,), jnp.bool_))
        self._emitted = self._dev(jnp.zeros((slots,), jnp.int32))
        self._max_new = self._dev(jnp.zeros((slots,), jnp.int32))
        self._keys = self._dev(jnp.zeros((slots, 2), jnp.uint32))
        self._temp = self._dev(jnp.zeros((slots,), jnp.float32))
        self._topk = self._dev(jnp.zeros((slots,), jnp.int32))
        self._topp = self._dev(jnp.ones((slots,), jnp.float32))

    # -- jitted programs -----------------------------------------------------

    def _jit_step(self, greedy_only: bool):
        """jit (single-device) or jit(shard_map) (mesh) of the step body.
        Carries ride replicated (``P()``); the paged pool is heads-
        sharded; the page table is ``data``-sharded when the slot batch
        is. Donation tuples match the historical single-device jits."""
        fn = self._make_step(greedy_only)
        donate = (1, 2, 3, 4, 5, 7)
        if self._plan is None:
            return jax.jit(fn, donate_argnums=donate)
        rep, kv = P(), tp.kv_specs(self._plan)
        pt = P("data", None) if self._plan.batch else rep
        in_specs = (self._pspecs, kv) + (rep,) * 9 + (pt,)
        out_specs = (kv, rep, rep, rep, rep, rep, (rep, rep))
        return tp.wrap(self._plan, fn, in_specs, out_specs, donate)

    def _jit_admit(self, greedy_only: bool):
        fn = self._make_admit(greedy_only)
        donate = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10)
        if self._plan is None:
            return jax.jit(fn, donate_argnums=donate)
        rep, kv = P(), tp.kv_specs(self._plan)
        # prompt/scalars/pages are all replicated: prefill's batch of one
        # never splits over ``data``; weights shard it over ``model``
        in_specs = (self._pspecs, kv) + (rep,) * (9 + 10)
        out_specs = (kv,) + (rep,) * 10
        return tp.wrap(self._plan, fn, in_specs, out_specs, donate)

    def _jit_admit_suffix(self, greedy_only: bool):
        fn = self._make_admit_suffix(greedy_only)
        donate = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10)
        if self._plan is None:
            return jax.jit(fn, donate_argnums=donate)
        rep, kv = P(), tp.kv_specs(self._plan)
        in_specs = (self._pspecs, kv) + (rep,) * (9 + 12)
        out_specs = (kv,) + (rep,) * 10
        return tp.wrap(self._plan, fn, in_specs, out_specs, donate)

    def _jit_restore(self):
        fn = self._make_restore()
        donate = (0, 1, 2, 3, 4, 5, 6, 7, 8, 9)
        if self._plan is None:
            return jax.jit(fn, donate_argnums=donate)
        rep, kv = P(), tp.kv_specs(self._plan)
        # ``saved`` (the host swap payload) shares the pool's kv_heads
        # axis 3, so each shard writes back only its own head slice
        in_specs = (kv,) + (rep,) * 9 + (kv,) + (rep,) * 10
        out_specs = (kv,) + (rep,) * 9
        return tp.wrap(self._plan, fn, in_specs, out_specs, donate)

    def _jit_cow(self):
        def cow(cache, src, dst):
            return registry.copy_pages(self.cfg, cache, src, dst,
                                       self.page_size)

        if self._plan is None:
            return jax.jit(cow, donate_argnums=(0,))
        rep, kv = P(), tp.kv_specs(self._plan)
        # per-shard page copy: each model shard copies its head slice
        return tp.wrap(self._plan, cow, (kv, rep, rep), kv, (0,))

    def _make_step(self, greedy_only: bool):
        vocab, max_seq = self.cfg.vocab, self.max_seq
        cm, paged = self.cm, self.paged

        def body(params, cache, token, pos, active, emitted, max_new,
                 keys, temp, topk, topp, page_table=None):
            # a slot that holds no request attends to nothing: its pos
            # keeps counting, and the paged kernel would walk that far
            kv_len = jnp.where(active, pos + 1, 0) if paged else None
            logits, cache = cm.decode(params, cache, token, pos, page_table,
                                      kv_len=kv_len)
            if greedy_only:
                # all-greedy specialization: no resident request can draw,
                # so the step is the historical bare argmax — the sampling
                # machinery (sorts, softmax, per-slot Gumbel over the
                # vocab) never enters the hot path. The engine retraces
                # once with greedy_only=False if a non-greedy request is
                # ever admitted.
                nxt = jnp.argmax(logits[:, :vocab], axis=-1) \
                    .astype(jnp.int32)
            else:
                # fused per-slot sampling over the whole pool (masked
                # slots draw a token too — exactly like the host engine's
                # unconditional argmax — so families whose decode couples
                # slots, e.g. MoE capacity routing, see an identical pool
                # state). temperature==0 rows are the historical argmax;
                # ``emitted`` is the stream index folded into the key.
                # Under a data-sharded mesh plan the logits rows are this
                # shard's slots only, so the key/param carries slice down
                # to match — the draw itself stays per-slot.
                nxt = sample_tokens(logits[:, :vocab], tp.data_shard(keys),
                                    tp.data_shard(emitted),
                                    tp.data_shard(temp),
                                    tp.data_shard(topk),
                                    tp.data_shard(topp))
            # the decode step's single cross-``data`` exchange: gather the
            # per-slot token back to the full slot axis (identity off-mesh)
            # — stop conditions and the emit pair then stay replicated
            nxt = tp.gather_data(nxt)
            new_pos = pos + 1
            new_emitted = emitted + active.astype(jnp.int32)
            done = active & ((new_emitted >= max_new)
                             | (new_pos >= max_seq - 1))
            new_active = active & ~done
            # the emit pair is computed DIFFERENTLY from the state outputs
            # so its buffers never alias state buffers donated into the
            # next dispatch while the host still holds the emit
            emit_tok = jnp.where(active, nxt, -1)
            return (cache, nxt, new_pos, new_active, new_emitted, keys,
                    (emit_tok, done))

        if paged:
            # the page table is a host-owned np array re-sent each dispatch
            # (tiny: slots * pages_per_slot i32) — NOT donated
            def fused(params, cache, token, pos, active, emitted, max_new,
                      keys, temp, topk, topp, page_table):
                return body(params, cache, token, pos, active, emitted,
                            max_new, keys, temp, topk, topp, page_table)
        else:
            def fused(params, cache, token, pos, active, emitted, max_new,
                      keys, temp, topk, topp):
                return body(params, cache, token, pos, active, emitted,
                            max_new, keys, temp, topk, topp)
        return fused

    def _jit_step_spec(self):
        """jit (or jit(shard_map)) of the fused draft-verify step. Same
        donation tuple and carry layout as the plain step; ``drafts``
        rides replicated like the sampling-parameter buffers, and the
        emit pair widens to ``([B, k+1] tokens, [B] done)``."""
        fn = self._make_step_spec()
        donate = (1, 2, 3, 4, 5, 7)
        if self._plan is None:
            return jax.jit(fn, donate_argnums=donate)
        rep, kv = P(), tp.kv_specs(self._plan)
        pt = P("data", None) if self._plan.batch else rep
        in_specs = (self._pspecs, kv) + (rep,) * 9 + (pt, rep)
        out_specs = (kv, rep, rep, rep, rep, rep, (rep, rep))
        return tp.wrap(self._plan, fn, in_specs, out_specs, donate)

    def _make_step_spec(self):
        """Draft-k-verify-once fused into one program: ``k + 1``
        sequential inner decode steps score the carry token and every
        draft position, acceptance is computed on-device, and each inner
        step's KV write is masked by its own commit flag — rejected
        positions land on the trap page, so the paged pool never sees a
        rejected token.

        Inner step ``j`` feeds ``x_j`` (``x_0`` = the carry token,
        ``x_j`` = draft ``j``) at position ``pos + j`` — the exact
        computation the plain step would run at that point — and emits
        ``t_j = argmax``. Draft ``j`` is accepted while every earlier
        draft was and it equals ``t_{j-1}`` (the token the target just
        emitted), so commit flags are prefix-contiguous and the
        committed stream is bitwise identical to target-only decoding.
        ``j < budget`` caps commits at the request's remaining token /
        sequence budget, mirroring the host's page lookahead. The new
        carry is the last committed ``t_j``; pos/emitted advance by the
        per-slot acceptance count ``e`` in [1, k+1]."""
        vocab, max_seq = self.cfg.vocab, self.max_seq
        cm, k = self.cm, self.spec.k

        def spec_step(params, cache, token, pos, active, emitted,
                      max_new, keys, temp, topk, topp, page_table,
                      drafts):
            budget = jnp.minimum(max_new - emitted, (max_seq - 1) - pos)
            flag = active               # flag_0: the carry always commits
            x = carry = token
            prev_t = token
            emits, commits = [], []
            for j in range(k + 1):
                if j > 0:
                    d_j = drafts[:, j - 1]
                    flag = flag & (d_j == prev_t) & (j < budget)
                    x = d_j
                logits, cache = cm.decode(params, cache, x, pos + j,
                                          page_table, write_mask=flag)
                t_j = jnp.argmax(logits[:, :vocab], axis=-1) \
                    .astype(jnp.int32)
                t_j = tp.gather_data(t_j)
                carry = jnp.where(flag, t_j, carry)
                emits.append(jnp.where(flag, t_j, -1))
                commits.append(flag)
                prev_t = t_j
            e = sum(c.astype(jnp.int32) for c in commits)
            new_pos = pos + e
            new_emitted = emitted + e
            done = active & ((new_emitted >= max_new)
                             | (new_pos >= max_seq - 1))
            new_active = active & ~done
            emit_tok = jnp.stack(emits, axis=1)          # [B, k+1]
            return (cache, carry, new_pos, new_active, new_emitted,
                    keys, (emit_tok, done))

        return spec_step

    def _make_admit(self, greedy_only: bool):
        cfg, vocab = self.cfg, self.cfg.vocab
        encdec = cfg.family == "encdec"
        pad_ok = self._pad_ok
        cm, paged = self.cm, self.paged

        def body(params, cache, token, pos, active, emitted, max_new,
                 keys, temp, topk, topp, prompt, length, slot, req_max_new,
                 req_emitted, seed, s_temp, s_topk, s_topp, pages=None):
            # req_emitted carries the cumulative emit count across requeues
            # (recompute preemption: the generated prefix is already in the
            # prompt and in out_tokens) — it is also the sampling index of
            # the token this prefill emits, minus one. ``pages`` (paged
            # only) is the physical destination of each logical prompt
            # page, trap-padded to the bucket, so the compile key stays
            # (bucket shape).
            logits, kv = registry.prefill(
                params, cfg, prompt[None],
                length=length if pad_ok else None)
            cache = cm.write(cache, kv, slot=slot, pages=pages)
            key = jax.random.PRNGKey(seed)
            if greedy_only:
                # all-greedy specialization, mirroring _make_step: tok0 is
                # the historical bare argmax; the key/param buffers are
                # still written so a later greedy_only=False retrace sees
                # a consistent carry
                tok0 = jnp.argmax(logits[0, :vocab]).astype(jnp.int32)
            else:
                tok0 = sample_tokens(logits[:, :vocab], key[None],
                                     (req_emitted - 1)[None], s_temp[None],
                                     s_topk[None], s_topp[None])[0]
            start = jnp.int32(1) if encdec else length
            token = token.at[slot].set(tok0)
            pos = pos.at[slot].set(start)
            active = active.at[slot].set(True)
            emitted = emitted.at[slot].set(req_emitted)
            max_new = max_new.at[slot].set(req_max_new)
            keys = keys.at[slot].set(key)
            temp = temp.at[slot].set(s_temp)
            topk = topk.at[slot].set(s_topk)
            topp = topp.at[slot].set(s_topp)
            return (cache, token, pos, active, emitted, max_new, keys,
                    temp, topk, topp, tok0)

        if paged:
            def admit(params, cache, token, pos, active, emitted, max_new,
                      keys, temp, topk, topp, prompt, length, slot,
                      req_max_new, req_emitted, seed, s_temp, s_topk,
                      s_topp, pages):
                return body(params, cache, token, pos, active, emitted,
                            max_new, keys, temp, topk, topp, prompt,
                            length, slot, req_max_new, req_emitted, seed,
                            s_temp, s_topk, s_topp, pages)
        else:
            def admit(params, cache, token, pos, active, emitted, max_new,
                      keys, temp, topk, topp, prompt, length, slot,
                      req_max_new, req_emitted, seed, s_temp, s_topk,
                      s_topp):
                return body(params, cache, token, pos, active, emitted,
                            max_new, keys, temp, topk, topp, prompt,
                            length, slot, req_max_new, req_emitted, seed,
                            s_temp, s_topk, s_topp)
        return admit

    def _make_admit_suffix(self, greedy_only: bool):
        """Radix-hit admission: the prompt's first ``prefix_len`` positions
        are already resident (tree pages mapped read-only into the slot's
        table), so only the suffix is prefilled — against prefix rows
        gathered from the pool. ``prefix_pages`` is trap-padded to the
        full ``pages_per_slot`` and ``prefix_len``/``s_len`` are traced,
        so the compile key is the suffix bucket shape alone."""
        cfg, vocab = self.cfg, self.cfg.vocab
        cm = self.cm

        def admit(params, cache, token, pos, active, emitted, max_new,
                  keys, temp, topk, topp, suffix, s_len, prefix_len,
                  prefix_pages, suffix_pages, slot, req_max_new,
                  req_emitted, seed, s_temp, s_topk, s_topp):
            prefix = cm.read(cache, prefix_pages)
            logits, kv = registry.prefill_suffix(
                params, cfg, suffix[None], prefix,
                prefix_len=prefix_len, length=s_len)
            cache = cm.write(cache, kv, pages=suffix_pages)
            key = jax.random.PRNGKey(seed)
            if greedy_only:
                tok0 = jnp.argmax(logits[0, :vocab]).astype(jnp.int32)
            else:
                tok0 = sample_tokens(logits[:, :vocab], key[None],
                                     (req_emitted - 1)[None], s_temp[None],
                                     s_topk[None], s_topp[None])[0]
            start = prefix_len + s_len        # true prompt length
            token = token.at[slot].set(tok0)
            pos = pos.at[slot].set(start)
            active = active.at[slot].set(True)
            emitted = emitted.at[slot].set(req_emitted)
            max_new = max_new.at[slot].set(req_max_new)
            keys = keys.at[slot].set(key)
            temp = temp.at[slot].set(s_temp)
            topk = topk.at[slot].set(s_topk)
            topp = topp.at[slot].set(s_topp)
            return (cache, token, pos, active, emitted, max_new, keys,
                    temp, topk, topp, tok0)

        return admit

    def _make_restore(self):
        """Jitted swap-in: write a victim's saved pages back into (new)
        physical pages and restore its device slot state verbatim (the
        sampling key is rebuilt from the seed — it is a pure function of
        it, so the restored stream replays the same (seed, index) draws)."""
        cm = self.cm

        def restore(cache, token, pos, active, emitted, max_new, keys,
                    temp, topk, topp, saved, tok, dpos, demitted,
                    req_max_new, seed, s_temp, s_topk, s_topp, slot, pages):
            cache = cm.write(cache, saved, pages=pages)
            token = token.at[slot].set(tok)
            pos = pos.at[slot].set(dpos)
            active = active.at[slot].set(True)
            emitted = emitted.at[slot].set(demitted)
            max_new = max_new.at[slot].set(req_max_new)
            keys = keys.at[slot].set(jax.random.PRNGKey(seed))
            temp = temp.at[slot].set(s_temp)
            topk = topk.at[slot].set(s_topk)
            topp = topp.at[slot].set(s_topp)
            return (cache, token, pos, active, emitted, max_new, keys,
                    temp, topk, topp)

        return restore

    # -- request lifecycle ---------------------------------------------------

    @property
    def queue(self):
        """Back-compat view of the waiting queue (the scheduler; truthy
        while requests wait, len() = waiting count)."""
        return self.scheduler

    @property
    def _pool(self):
        """Back-compat handle to the paged allocator (None if contiguous)."""
        return self.cm.pool if self.paged else None

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        req.arrival = self._arrivals
        self._arrivals += 1
        if req.deadline_s is not None:
            self._has_deadlines = True
        msg = self._admission_error(req)
        if msg is not None:
            self._finish(req, "rejected", msg)
            return
        self.scheduler.push(req)

    def _admission_error(self, req: Request) -> Optional[str]:
        """Admission validation: the reason ``req`` can never be served
        (rejected up front, instead of wedging the FIFO head or blowing
        up inside a jitted prefill), or None when it is admissible."""
        prompt = np.asarray(req.prompt)
        n = len(prompt)
        if n == 0:
            return "empty prompt"
        if prompt.ndim == 1:           # token frontend
            if not np.issubdtype(prompt.dtype, np.integer):
                return ("token prompt must be integer-typed, got "
                        f"{prompt.dtype}")
            lo, hi = int(prompt.min()), int(prompt.max())
            if lo < 0 or hi >= self.cfg.vocab:
                return (f"token id {lo if lo < 0 else hi} outside "
                        f"[0, {self.cfg.vocab})")
        else:                          # frames frontend [S, D]
            if not np.all(np.isfinite(prompt)):
                return "non-finite values in frame prompt"
        if n > self.max_seq - 1:
            return (f"prompt length {n} cannot fit max_seq={self.max_seq} "
                    "(no room to emit a token)")
        if self.spec is not None:
            sp = req.sampling if req.sampling is not None \
                else self.default_sampling
            if not sp.greedy:
                return ("speculative decoding verifies drafts against "
                        "the greedy (argmax) target stream; non-greedy "
                        "sampling cannot serve with spec enabled")
        return self.cm.infeasible(n)

    def _finish(self, req: Request, reason: str,
                error: Optional[str] = None) -> None:
        """Terminal bookkeeping for every lifecycle outcome."""
        req.done = True
        req.finish_reason = reason
        req.error = error
        self.finished.append(req)
        if reason in self._lifecycle:
            self._lifecycle[reason] += 1

    def _cancel_resident(self, i: int, reason: str,
                         error: Optional[str] = None) -> None:
        """Pull slot ``i``'s occupant out of residency and finish it:
        deactivate the device slot (later dispatches route its masked
        writes to the trap page) and release its pages through the normal
        ``CacheManager.evict`` path — private pages free, tree-shared
        prefix pages survive through their radix refs. The caller must
        have drained the pending emit first (the overlapped readback
        snapshot must not resurrect the request)."""
        assert self._pending is None
        slot = self.slots[i]
        req = slot.req
        slot.req = None
        slot.dactive = False
        slot.dpos = slot.demitted = 0
        self._active = self._active.at[i].set(False)
        self.cm.evict(i)
        self._finish(req, reason, error)

    def abort(self, rid: int, *, reason: str = "aborted",
              error: Optional[str] = None) -> bool:
        """Cancel the live request named ``rid`` wherever it currently
        lives — waiting (including swapped-out preemption victims) or
        resident mid-decode. True when a live request was found; the
        request is finished (usually ``finish_reason="aborted"``) when
        the call returns. A resident target is settled through a drain
        first, so an abort that races the natural finish resolves to
        whichever happened first."""
        for req in self.scheduler.waiting():
            if req.rid == rid and not req.done:
                self.scheduler.remove(req)
                req.swap_state = None    # swapped victim: pages were freed
                self._finish(req, reason, error)
                return True
        for i, slot in enumerate(self.slots):
            if slot.req is not None and slot.req.rid == rid:
                self._drain()
                if self.slots[i].req is not None \
                        and self.slots[i].req.rid == rid:
                    self._cancel_resident(i, reason, error)
                return True
        return False

    def cancel_request(self, req: Request, reason: str = "aborted",
                       error: Optional[str] = None) -> bool:
        """``abort`` by identity instead of rid (the facade's handle)."""
        if req.done:
            return False
        if self.scheduler.remove(req):
            req.swap_state = None
            self._finish(req, reason, error)
            return True
        for i, slot in enumerate(self.slots):
            if slot.req is req:
                self._drain()
                if self.slots[i].req is req:
                    self._cancel_resident(i, reason, error)
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Finish every request whose wall-clock budget ran out — waiting
        requests leave the queue, resident ones are cancelled through the
        same rollback path as ``abort``."""
        now = time.perf_counter()

        def expired(req):
            return (req.deadline_s is not None
                    and now - req.t_submit >= req.deadline_s)

        for req in self.scheduler.waiting():
            if expired(req):
                self.scheduler.remove(req)
                req.swap_state = None
                self._finish(req, "deadline")
        if any(s.req is not None and expired(s.req) for s in self.slots):
            self._drain()
            for i, slot in enumerate(self.slots):
                if slot.req is not None and expired(slot.req):
                    self._cancel_resident(i, "deadline")

    def _sampling_of(self, req: Request) -> SamplingParams:
        sp = req.sampling if req.sampling is not None \
            else self.default_sampling
        if self._greedy_only and not sp.greedy:
            # first non-greedy admission: swap the all-greedy specialized
            # step/admit programs for the sampling ones (one retrace per
            # program + bucket; the carry layout is identical, so
            # in-flight state is unaffected)
            self._greedy_only = False
            self._step_fn = self._jit_step(False)
            self._compiles_base += self._admit_fn._cache_size()
            self._admit_fn = self._jit_admit(False)
            if self._prefix_cache:
                self._compiles_base += self._admit_suffix_fn._cache_size()
                self._admit_suffix_fn = self._jit_admit_suffix(False)
        return sp

    def _bucket_len(self, n: int) -> Optional[int]:
        """Padded prompt length, or None for an exact-length prefill."""
        if not self._pad_ok:
            return None
        cap = min(self.max_seq, self.cfg.window or self.max_seq)
        if n > cap:
            return None            # longer than the paddable window: exact
        b = 1
        while b < n:
            b *= 2
        return min(b, cap)

    def _suffix_bucket(self, s_len: int) -> int:
        """Suffix-prefill bucket: pow2 like ``_bucket_len`` but floored at
        one page, so tiny suffixes (the common radix-hit case) all share
        one compiled program instead of one per pow2 below page_size."""
        b = self._bucket_len(s_len)
        return max(self.page_size, b if b is not None else s_len)

    def _readmit_swapped(self, i: int, slot: _Slot, req: Request) -> bool:
        """Swap-in re-admission: restore the victim's saved pages + device
        state byte-for-byte (no prefill, no token emitted). False when the
        pool cannot hold the pages yet (head-of-line waits)."""
        saved, tok, dpos, demitted, n_real, draft_saved = req.swap_state
        if not self.cm.restore(i, n_real):
            return False
        self.scheduler.pop()
        pages = jnp.asarray(self.cm.pages_of(i))
        sp = self._sampling_of(req)
        try:
            out = self._dispatch_restore(i, req, sp, pages)
        except RuntimeError as e:
            # failure isolation: a faulted swap-in fails this request
            # alone (the hold rolls back; the slot refills next step)
            self.cm.evict(i)
            req.swap_state = None
            self._finish(req, "failed", f"swap-restore fault: {e}")
            return True
        (self.cache, self._token, self._pos, self._active, self._emitted,
         self._max_new, self._keys, self._temp, self._topk,
         self._topp) = out
        if draft_saved is not None and self._drafter is not None:
            # drafter state comes back byte-for-byte with the target's
            # pages, so the restored stream's draft proposals replay
            # exactly as an undisturbed run's would
            self._drafter.restore_slot(i, draft_saved)
        req.swap_state = None
        slot.req = req
        slot.dpos = dpos
        slot.demitted = demitted
        slot.dactive = True
        return True

    def _dispatch_restore(self, i: int, req: Request, sp, pages):
        saved, tok, dpos, demitted = req.swap_state[:4]
        return self._dispatch(
            self._restore_fn, self.cache, self._token, self._pos,
            self._active, self._emitted, self._max_new, self._keys,
            self._temp, self._topk, self._topp,
            jax.tree.map(jnp.asarray, saved), jnp.int32(tok),
            jnp.int32(dpos), jnp.int32(demitted),
            jnp.int32(req.max_new_tokens),
            jnp.int32(sp.resolve_seed(req.rid)),
            jnp.float32(sp.temperature), jnp.int32(sp.top_k),
            jnp.float32(sp.top_p), jnp.int32(i), pages)

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot.req is None and len(self.scheduler):
                req = self.scheduler.peek()
                with jax.profiler.TraceAnnotation("engine.admit",
                                                  rid=req.rid):
                    if not self._admit_head(i, slot, req):
                        return     # head-of-line: admission waits for pages

    def _admit_head(self, i: int, slot: _Slot, req: Request) -> bool:
        """Admit the head of the queue, ``req``, into the free slot ``i``.
        False when the pool cannot hold it yet (it stays queued)."""
        if self.paged and req.swap_state is not None:
            return self._readmit_swapped(i, slot, req)
        prompt = np.asarray(req.prompt)
        if req.out_tokens:
            # recompute re-admission after preemption: the generated
            # prefix joins the prompt, so prefill rebuilds the exact
            # logical cache the victim lost
            prompt = np.concatenate(
                [prompt, np.asarray(req.out_tokens, prompt.dtype)])
        n = len(prompt)
        b = self._bucket_len(n)
        if self._prefix_cache:
            # radix-aware hold: maps the longest cached prefix
            # read-only + reserves private pages for the rest
            plan = self.cm.admit_prompt(i, prompt)
            if plan is None:
                return False
        else:
            plan = None
            if not self.cm.alloc(i, n):
                return False
        self.scheduler.pop()
        if not req.t_admit:
            req.t_admit = time.perf_counter()
        sp = self._sampling_of(req)
        try:
            if plan is not None and plan["suffix_start"] > 0:
                tok0 = self._dispatch_suffix(i, req, prompt, n,
                                             plan, sp)
                req.prefix_hit_tokens += plan["suffix_start"]
            else:
                pages_arg = None
                if self.paged:
                    pages_arg = jnp.asarray(
                        self.cm.prefill_pages(i, n, b))
                if b is not None and b > n:
                    pad = np.zeros((b - n,) + prompt.shape[1:],
                                   prompt.dtype)
                    prompt = np.concatenate([prompt, pad])
                args = (self.params, self.cache, self._token,
                        self._pos, self._active, self._emitted,
                        self._max_new, self._keys, self._temp,
                        self._topk, self._topp, jnp.asarray(prompt),
                        jnp.int32(n), jnp.int32(i),
                        jnp.int32(req.max_new_tokens),
                        jnp.int32(len(req.out_tokens) + 1),
                        jnp.int32(sp.resolve_seed(req.rid)),
                        jnp.float32(sp.temperature),
                        jnp.int32(sp.top_k), jnp.float32(sp.top_p))
                if self.paged:
                    args += (pages_arg,)
                out = self._dispatch(self._admit_fn, *args)
                (self.cache, self._token, self._pos, self._active,
                 self._emitted, self._max_new, self._keys,
                 self._temp, self._topk, self._topp, tok0) = out
            if self._drafter is not None:
                # the drafter mirrors the FULL prompt (generated
                # prefix included on recompute re-admission, the
                # radix-served prefix included on suffix hits —
                # the draft cache has no page sharing), so its
                # carry invariant matches the target's exactly
                self._drafter.prefill(i, prompt[:n])
        except RuntimeError as e:
            # failure isolation: a faulted prefill (XLA launch /
            # runtime error) fails this request alone — its
            # admission hold rolls back and the slot refills on
            # the next step (deactivated in case the fault hit
            # after the target admit already marked it active)
            self._active = self._active.at[i].set(False)
            self.cm.evict(i)
            self._finish(req, "failed", f"prefill fault: {e}")
            return True
        if self.paged:
            # the prompt's full pages are now written (prefill
            # covers 0..n-1) — publish them to the radix tree so
            # later admissions can share them (no-op when disabled)
            self.cm.insert_prompt(i, prompt[:n], n)
        was_requeued = bool(req.out_tokens)
        with jax.profiler.TraceAnnotation("engine.first_token"):
            req.out_tokens.append(int(tok0))
        if not req.t_first:
            req.t_first = time.perf_counter()
        if self.paged and was_requeued \
                and (len(req.out_tokens) >= req.max_new_tokens
                     or n >= self.max_seq - 1):
            # Recompute re-admission delivered the request's FINAL
            # token: in the straight-through run this token came
            # from the decode step that fired the stop condition,
            # so it must not decode again. (A fresh admission never
            # checks — the reference engine always decodes at least
            # one step after prefill.)
            self._finish(req, "done")
            self._active = self._active.at[i].set(False)
            self.cm.evict(i)
            return True
        slot.req = req
        slot.dpos = 1 if self.cfg.family == "encdec" else n
        slot.demitted = len(req.out_tokens)
        slot.dactive = True
        return True

    def _dispatch_suffix(self, i: int, req: Request, prompt: np.ndarray,
                         n: int, plan: dict, sp) -> int:
        """Dispatch a radix-hit admission: optional copy-on-write page
        duplication, then the suffix-only prefill program."""
        ss = plan["suffix_start"]
        s_len = n - ss
        sb = self._suffix_bucket(s_len)
        suffix = prompt[ss:]
        if sb > s_len:
            pad = np.zeros((sb - s_len,) + suffix.shape[1:], suffix.dtype)
            suffix = np.concatenate([suffix, pad])
        if plan["cow"] is not None:
            # a full-prompt match re-prefills its final page into a fresh
            # private copy; duplicate the shared page's bytes first so the
            # copy also holds rows the suffix program won't rewrite
            src, dst = plan["cow"]
            self.cache = self._dispatch(self._cow_fn, self.cache,
                                        jnp.int32(src), jnp.int32(dst))
        self._suffix_shapes.add(sb)
        args = (self.params, self.cache, self._token, self._pos,
                self._active, self._emitted, self._max_new,
                self._keys, self._temp, self._topk, self._topp,
                jnp.asarray(suffix), jnp.int32(s_len), jnp.int32(ss),
                jnp.asarray(self.cm.prefix_page_vec(i, ss)),
                jnp.asarray(self.cm.suffix_pages(i, ss, n, sb)),
                jnp.int32(i), jnp.int32(req.max_new_tokens),
                jnp.int32(len(req.out_tokens) + 1),
                jnp.int32(sp.resolve_seed(req.rid)),
                jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                jnp.float32(sp.top_p))
        out = self._dispatch(self._admit_suffix_fn, *args)
        (self.cache, self._token, self._pos, self._active,
         self._emitted, self._max_new, self._keys, self._temp,
         self._topk, self._topp, tok0) = out
        return tok0

    # -- paged pool growth / preemption --------------------------------------

    def _preempt(self, victim: int) -> None:
        """Evict the occupant of ``victim``: free its residency, deactivate
        the device slot, and hand the request back to the scheduler with
        requeue precedence. The ``PreemptionPolicy`` decides what happens
        to the KV: ``"swap"`` first copies the victim's pages and device
        state to host for a byte-exact swap-in later; ``"recompute"``
        drops them — re-admission folds the generated prefix into the
        prompt and re-prefills. Caller must have drained the pending emit
        (the victim's stream must be settled before its pages are
        reused)."""
        assert self._pending is None
        slot = self.slots[victim]
        req = slot.req
        if self.preemption.mode == "swap":
            owned = self.cm.pages_of(victim)
            saved = self.cm.read(self.cache, jnp.asarray(owned))
            draft_saved = self._drafter.snapshot_slot(victim) \
                if self._drafter is not None else None
            req.swap_state = (
                jax.tree.map(np.asarray, saved),      # host copy (swap out)
                int(np.asarray(self._token)[victim]),
                slot.dpos, slot.demitted, len(owned), draft_saved)
        self.cm.evict(victim)
        slot.req = None
        slot.dactive = False
        self._active = self._active.at[victim].set(False)
        req.preemptions += 1
        self.preemptions += 1
        self.scheduler.requeue(req)

    def _ensure_pages(self) -> None:
        """Before a dispatch, make every device-active slot's next write
        position storage-backed. On pool exhaustion: settle the in-flight
        step (finished slots free pages), then let the preemption policy
        pick a victim (youngest occupant by default) until the write
        fits. Under speculative decoding a step may commit up to ``k+1``
        positions, so the lookahead covers the slot's worst-case commit
        (capped by its remaining token/sequence budget — the device's
        ``j < budget`` commit gate mirrors exactly this bound, so no
        committed write can ever land on an unbacked page)."""
        with jax.profiler.TraceAnnotation("engine.ensure_pages"):
            for i in range(self.n_slots):
                slot = self.slots[i]
                if slot.req is None or not slot.dactive:
                    continue
                need = 1
                if self.spec is not None:
                    budget = min(slot.req.max_new_tokens - slot.demitted,
                                 (self.max_seq - 1) - slot.dpos)
                    need = max(1, min(self.spec.k + 1, budget))
                while not self.cm.backed(i, slot.dpos + need - 1):
                    if self.cm.grow(i):
                        continue
                    self._drain()
                    if self.slots[i].req is None or not self.slots[i].dactive:
                        break              # the drain settled this very slot
                    if self.cm.has_free:
                        continue           # the drain freed finished slots
                    occ = [(j, self.slots[j].req) for j in range(self.n_slots)
                           if self.slots[j].req is not None]
                    victim = self.preemption.select_victim(occ)
                    self._preempt(victim)
                    if victim == i:
                        break              # preempted ourselves; requeued

    # -- failure isolation / crash recovery ----------------------------------

    def _reject_unadmittable_head(self) -> bool:
        """Infeasibility watchdog: the engine is quiescent (no resident
        slot, nothing in flight) yet the head of line was not admitted.
        If the head can NEVER fit — page demand exceeding the whole pool
        or the sequence budget — reject it instead of deadlocking every
        request behind it. Transient causes (chaos page holds, custom
        managers withholding capacity) return False and leave the head
        queued."""
        req = self.scheduler.peek()
        if req is None or req.swap_state is not None:
            return False               # swapped victims always fit again
        n = len(req.prompt) + len(req.out_tokens)
        if n > self.max_seq - 1:
            msg = (f"sequence length {n} cannot fit max_seq="
                   f"{self.max_seq} (no room to emit a token)")
        else:
            msg = self.cm.infeasible(n)
        if msg is None:
            return False
        self.scheduler.remove(req)      # not an admission: no pop stats
        self._finish(req, "rejected", msg)
        return True

    def _recover_step_fault(self, exc: BaseException) -> None:
        """Crash-consistent rollback after a faulted decode dispatch.

        The fault surfaced *in place of* the dispatch (a failed XLA
        launch — or the chaos harness's stand-in for one — leaves its
        donated inputs unconsumed), so carry buffers and cache still hold
        the valid pre-step state. Sequence: settle the overlapped emit
        (it predates the fault), quarantine the faulting slot's request
        (``exc.slot`` when the fault names one, else the preemption
        policy's victim), swap every surviving occupant's pages + device
        state to host byte-for-byte, reset the device pool and carry
        outright, and requeue the survivors — their restored streams
        finish bit-identical to an undisturbed run. If the carry WAS lost
        with the fault (mid-kernel device failure), the byte-exact read
        raises and survivors fall back to recompute (token frontends) or
        fail (frames)."""
        self._drain()
        bad = getattr(exc, "slot", None)
        if bad is not None and not (0 <= bad < self.n_slots
                                    and self.slots[bad].req is not None):
            bad = None
        occ = [(i, s.req) for i, s in enumerate(self.slots)
               if s.req is not None]
        if bad is None and occ:
            bad = self.preemption.select_victim(occ)
        survivors: list[Request] = []
        for i, slot in enumerate(self.slots):
            req = slot.req
            if req is None or i == bad:
                continue
            req.swap_state = None
            if self.paged:
                try:
                    # byte-exact swap-out BEFORE the pool reset — restore
                    # then replays the exact device state, keeping the
                    # survivor's stream bit-identical
                    owned = self.cm.pages_of(i)
                    saved = self.cm.read(self.cache, jnp.asarray(owned))
                    draft_saved = (
                        self._drafter.snapshot_slot(i)
                        if self._drafter is not None
                        and self._drafter.stateful else None)
                    req.swap_state = (
                        jax.tree.map(np.asarray, saved),
                        int(np.asarray(self._token)[i]),
                        slot.dpos, slot.demitted, len(owned), draft_saved)
                except RuntimeError:
                    req.swap_state = None   # carry died with the fault
            if req.swap_state is None \
                    and np.asarray(req.prompt).ndim != 1:
                # frames frontend without a byte-exact copy: generated
                # tokens cannot be folded back into a float prompt
                self._finish(req, "failed",
                             f"lost to device-fault recovery: {exc}")
                slot.req = None
                continue
            req.preemptions += 1
            survivors.append(req)
        for i, slot in enumerate(self.slots):
            req, slot.req = slot.req, None
            slot.dactive = False
            slot.dpos = slot.demitted = 0
            self.cm.evict(i)
            if req is not None and i == bad:
                self._finish(req, "failed", f"device step fault: {exc}")
        # reversed: slot 0's occupant ends up at the head of the queue,
        # so re-admission preserves the slot order survivors held
        for req in reversed(survivors):
            self.scheduler.requeue(req)
        if self.paged:
            # the radix tree's cached KV died with the pool
            self.cm.clear_tree()
            self.cm.pool.check()
        # rebuild the device-side state (same shapes and shardings: no
        # retrace, and mesh placements survive the recovery)
        self.cache = self._new_cache()
        self._fresh_carries()
        if self._drafter is not None:
            # the draft cache shares the device that faulted: drop it and
            # replay survivors' drafter rows from their snapshots on
            # re-admission (byte-for-byte, like the target pages)
            self._drafter.reset()
        self.recoveries += 1

    # -- one engine step -----------------------------------------------------

    def has_work(self) -> bool:
        """True while anything is queued, in flight, or resident."""
        return bool(len(self.scheduler) or self._pending is not None
                    or any(s.req is not None for s in self.slots))

    def step(self) -> bool:
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=self._steps):
            return self._step()

    def _step(self) -> bool:
        step_no = self._steps
        if self.chaos is not None:
            self.chaos.on_step(self, step_no)
        if self._has_deadlines:
            self._expire_deadlines()
        if self._pending is not None and \
                (len(self.scheduler)
                 and all(s.req is not None for s in self.slots)
                 or all(s.req is None or not s.dactive
                        for s in self.slots)):
            # Catch up on the pending emit when it can change what to do
            # next: either its done flags may free slots for the waiting
            # queue (admission timing then matches the host-driven engine
            # under queue pressure), or EVERY occupied slot finishes inside
            # it — dispatching before applying would burn one all-masked
            # decode step at the tail of each run.
            self._drain()
        self._admit()
        if self.paged:
            self._ensure_pages()
        if not any(s.req is not None for s in self.slots):
            self._drain()
            self._admit()
            if self.paged:
                self._ensure_pages()
            if not any(s.req is not None for s in self.slots):
                if len(self.scheduler):
                    # quiescent with a wedged head of line: reject it if
                    # it can never be admitted (deadlock watchdog) …
                    if self._reject_unadmittable_head():
                        return True
                    # … or end a chaos page hold that alone blocks
                    # progress, and retry on the next step
                    if self.chaos is not None and self.chaos.relent(self):
                        return True
                return False
        fault = None
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            args = self._step_args()
            try:
                if self.chaos is not None:
                    # BEFORE the draft propose: an injected fault then
                    # leaves the drafter's donated cache unconsumed,
                    # exactly like the target carries
                    self.chaos.pre_dispatch(self, step_no)
                if self.spec is not None:
                    drafts = self._drafter.propose(self.slots, self._token,
                                                   self._pos)
                    out = self._dispatch(self._spec_step_fn, *args,
                                         jnp.asarray(drafts))
                else:
                    out = self._dispatch(self._step_fn, *args)
            except RuntimeError as e:  # XlaRuntimeError subclasses this
                fault = e
        if fault is not None:
            self._recover_step_fault(fault)
            return True
        (self.cache, self._token, self._pos, self._active,
         self._emitted, self._keys, emit) = out
        if self.chaos is not None:
            emit = self.chaos.filter_emit(step_no, emit)
        self._steps += 1
        if self.spec is not None:
            # variable acceptance: the host shadows can only advance from
            # the actual commit counts, so spec mode settles every step
            # immediately (no readback overlap). The one-batched-readback-
            # per-step invariant is untouched — exactly one _apply_spec per
            # dispatched step, and readbacks == steps stays exact-gated.
            self._apply_spec((emit, [s.req for s in self.slots]))
            self._sample_page_stats()
            return True
        # mirror the device's deterministic stop conditions on the host
        # shadows (the readback of this step is still in flight)
        for s in self.slots:
            if s.req is not None and s.dactive:
                s.demitted += 1
                s.dpos += 1
                if (s.demitted >= s.req.max_new_tokens
                        or s.dpos >= self.max_seq - 1):
                    s.dactive = False
        if self.paged:
            self._sample_page_stats()
        prev, self._pending = self._pending, (emit,
                                              [s.req for s in self.slots])
        if prev is not None:
            self._apply(prev)           # readback of step k-1 overlaps k
        return True

    def _step_args(self) -> tuple:
        """The plain decode step's arguments as the next dispatch would
        pass them (carries, then the cache manager's page table)."""
        return (self.params, self.cache, self._token, self._pos,
                self._active, self._emitted, self._max_new, self._keys,
                self._temp, self._topk, self._topp) \
            + tuple(jnp.asarray(x) for x in self.cm.step_extra())

    def step_program_text(self) -> str:
        """StableHLO of the decode step program the engine dispatches now
        (lowered, not compiled or run) — e.g. to check that its kernels
        are Pallas custom calls and not the jnp references."""
        fn = self._spec_step_fn if self.spec is not None else self._step_fn
        args = self._step_args()
        if self.spec is not None:
            args += (jnp.zeros((self.n_slots, self.spec.k), jnp.int32),)
        return fn.lower(*args).as_text()

    def _sample_page_stats(self):
        rows = {i: min(s.dpos, self.max_seq)
                for i, s in enumerate(self.slots) if s.req is not None}
        self.cm.note_step(rows)

    def flush(self):
        """Settle the in-flight readback (public form of the drain the
        run loop does at exit — the streaming facade calls this)."""
        self._drain()

    def _drain(self):
        if self._pending is not None:
            prev, self._pending = self._pending, None
            self._apply(prev)

    def _apply(self, pending):
        (emit_tok, done), reqs = pending
        # THE host readback: one batched device->host transfer settles a
        # whole dispatched step (sharded runs included — the emit pair is
        # replicated by construction, so no extra per-shard transfers).
        # Counted so the bench CI can gate one-readback-per-step exactly.
        self._readbacks += 1
        with jax.profiler.TraceAnnotation("engine.readback"):
            tok = np.asarray(emit_tok)
            fin = np.asarray(done)
        for i, req in enumerate(reqs):
            if req is None or req.done or tok[i] == -1:
                # ``req.done``: a request quarantined by the corrupt-
                # readback path below was still device-active when the
                # overlapped NEXT snapshot was taken — its late tokens
                # must not resurrect the finished stream
                continue
            t = int(tok[i])
            if t < 0 or t >= self.cfg.vocab:
                # corrupt/NaN readback: a valid emit is -1 or a vocab id,
                # nothing else. Only this request is quarantined — the
                # other slots' device state is untouched, so their
                # streams continue undisturbed.
                if self.slots[i].req is req:
                    self.slots[i].req = None
                    self.slots[i].dactive = False
                    self._active = self._active.at[i].set(False)
                    self.cm.evict(i)
                self._finish(req, "failed",
                             f"corrupt readback: token {t} outside "
                             f"[0, {self.cfg.vocab})")
                continue
            req.out_tokens.append(t)
            if fin[i]:
                self._finish(req, "done")
                if self.slots[i].req is req:
                    if self._prefix_cache:
                        # publish the full sequence's pages before freeing
                        # them: coverage stops one short of the end — the
                        # final emitted token's KV row was never written
                        # (and the overlapped extra dispatch may write
                        # there), so only strictly-earlier full pages are
                        # valid
                        prompt = np.asarray(req.prompt)
                        toks = np.concatenate(
                            [prompt,
                             np.asarray(req.out_tokens, prompt.dtype)])
                        self.cm.insert_prompt(i, toks, len(toks) - 1)
                    self.slots[i].req = None
                    # (paged) later dispatches route this slot's masked
                    # writes to the trap page; its pages are safe to reuse
                    self.cm.evict(i)

    def _apply_spec(self, pending):
        """Settle a spec step: ONE batched readback of the ``[slots,
        k+1]`` commit matrix + done flags; each slot's host shadows then
        advance by its actual acceptance count. Commit rows are prefix-
        contiguous by construction (-1 past the accepted prefix), so the
        committed tokens are ``row[row != -1]`` and the per-request
        ordering matches target-only decoding bit for bit."""
        (emit_tok, done), reqs = pending
        self._readbacks += 1
        with jax.profiler.TraceAnnotation("engine.readback"):
            tok = np.asarray(emit_tok)
            fin = np.asarray(done)
        for i, req in enumerate(reqs):
            if req is None or req.done:
                continue
            row = tok[i]
            committed = row[row != -1]
            if committed.size == 0:
                continue        # slot idle this step: nothing committed
            if ((committed < 0) | (committed >= self.cfg.vocab)).any():
                # corrupt/NaN readback: quarantine this request only (the
                # plain path's contract — other slots' device state is
                # untouched and their streams continue undisturbed)
                if self.slots[i].req is req:
                    self.slots[i].req = None
                    self.slots[i].dactive = False
                    self._active = self._active.at[i].set(False)
                    self.cm.evict(i)
                bad = int(committed[
                    (committed < 0) | (committed >= self.cfg.vocab)][0])
                self._finish(req, "failed",
                             f"corrupt readback: token {bad} outside "
                             f"[0, {self.cfg.vocab})")
                continue
            e = int(committed.size)
            self._spec_slot_steps += 1
            self._spec_emitted += e
            req.accepted_tokens += e - 1    # e = 1 carry + (e-1) drafts
            req.out_tokens.extend(int(t) for t in committed)
            slot = self.slots[i]
            if slot.req is req and slot.dactive:
                slot.demitted += e
                slot.dpos += e
                if (slot.demitted >= req.max_new_tokens
                        or slot.dpos >= self.max_seq - 1):
                    slot.dactive = False
            if fin[i]:
                self._finish(req, "done")
                if slot.req is req:
                    if self._prefix_cache:
                        # identical coverage rule to the plain path: stop
                        # one short of the end — the final committed
                        # token's KV row was never written
                        prompt = np.asarray(req.prompt)
                        toks = np.concatenate(
                            [prompt,
                             np.asarray(req.out_tokens, prompt.dtype)])
                        self.cm.insert_prompt(i, toks, len(toks) - 1)
                    slot.req = None
                    self.cm.evict(i)

    def run(self, max_steps: int = 10_000):
        while max_steps > 0 and self.has_work():
            if not self.step():
                break
            max_steps -= 1
        self._drain()
        return self.finished

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        """Decode steps, prefill retrace count, bucket coverage, scheduler
        counters, the mesh plan (``describe()``), the KV pool's bytes on
        each device (``kv_pool_bytes_per_chip``: one shard on a mesh),
        and (paged) preemption, page-pool utilization/fragmentation and
        the attention's page walk (``attn_pages_walked_share``)."""
        prefill_compiles = self._compiles_base \
            + self._admit_fn._cache_size()
        if self._prefix_cache:
            prefill_compiles += self._admit_suffix_fn._cache_size()
        out = {
            "steps": self._steps,
            "readbacks": self._readbacks,
            "prefill_compiles": int(prefill_compiles),
            "suffix_shapes": sorted(self._suffix_shapes),
            "pad_prefill": self._pad_ok,
            "slots": self.n_slots,
            "paged": self.paged,
            "preemptions": self.preemptions,
            # request-lifecycle outcomes (exact-gated by the bench CI)
            "aborted": self._lifecycle["aborted"],
            "rejected": self._lifecycle["rejected"],
            "failed": self._lifecycle["failed"],
            "deadline_expired": self._lifecycle["deadline"],
            "recoveries": self.recoveries,
        }
        out.update(self.scheduler.stats())
        if self.spec_config is not None:
            # surfaced whenever spec was REQUESTED — an inert config
            # (contiguous cache, frames frontend) reports zeros, so the
            # bench twin rows stay shape-stable either way
            ss, emitted = self._spec_slot_steps, self._spec_emitted
            draft_tokens = ss * self.spec_config.k
            accepted = emitted - ss
            out["spec_on"] = self.spec is not None
            out["spec_drafter"] = self.spec_config.drafter
            out["spec_k"] = self.spec_config.k
            out["draft_tokens"] = draft_tokens
            out["accepted_tokens"] = accepted
            out["accepted_per_step"] = emitted / ss if ss else 0.0
            out["accept_rate"] = \
                accepted / draft_tokens if draft_tokens else 0.0
        if self._plan is not None:
            out["mesh"] = self._plan.describe()
        out["kv_pool_bytes_per_chip"] = int(sum(
            np.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(self.cache)))
        if self.chaos is not None:
            out.update(self.chaos.stats())
        if self.paged:
            out["preempt_mode"] = self.preempt_mode
            out.update(self.cm.stats())
            out["attn_pages_walked_share"] = self._pages_walked_share()
        return out

    def _pages_walked_share(self) -> float:
        """Pages the last decode step's attention walked, from the host
        shadows: each active slot's ``dpos`` rows (that step's length),
        over every slot's whole table — the walk before lengths bound
        it. Computed here, never per step."""
        page = self.page_size
        walked = sum(-(-min(s.dpos, self.max_seq) // page)
                     for s in self.slots if s.req is not None and s.dactive)
        return walked / (self.n_slots * (self.max_seq // page))
