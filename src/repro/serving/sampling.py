"""Sampling layer of the serving API: ``SamplingParams`` + the fused
on-device draw.

``SamplingParams`` is the per-request knob set (vLLM/SGLang-style):
``temperature == 0`` is greedy argmax — bit-identical to the historical
``Engine(greedy=True)`` path — and ``temperature > 0`` is a categorical
draw over the (optionally top-k / top-p truncated) softmax.

The draw itself, ``sample_tokens``, runs INSIDE the engine's donated fused
decode step: one vmapped per-slot draw over the whole pool, keyed by a
``jax.random`` key buffer that lives in the donated carry. Non-greedy
decode therefore costs the same one batched host readback per step as
greedy decode — no extra syncs.

Reproducibility: the key for a request's *t*-th output token is
``fold_in(PRNGKey(seed), t)`` — a pure function of ``(seed, t)``, not a
split chain threaded through dispatches. Streams are therefore
bit-identical across engine restarts, across the contiguous and paged
cache managers, and across swap preemption/restore (which replays the same
``(seed, t)`` pairs). When ``seed`` is None the engine derives it from the
request id, so concurrent requests diverge by default but every run of the
same request list is reproducible.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    temperature: 0.0 => greedy argmax (the default); > 0 scales logits
        before the categorical draw.
    top_k: keep only the k highest-logit tokens (0 => disabled).
    top_p: keep the smallest prefix of the sorted distribution whose
        cumulative probability reaches p (1.0 => disabled). Applied after
        top_k, per the usual convention.
    seed: per-request PRNG seed. None => the engine uses the request id,
        so distinct requests draw distinct noise but runs stay
        deterministic.
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature={self.temperature} must be >= 0")
        if self.top_k < 0:
            raise ValueError(f"top_k={self.top_k} must be >= 0 (0 disables)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p={self.top_p} must be in (0, 1]")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def resolve_seed(self, rid: int) -> int:
        """The effective per-request seed (request id when unset)."""
        return int(self.seed) if self.seed is not None else int(rid)


GREEDY = SamplingParams()


def sample_tokens(logits, keys, index, temperature, top_k, top_p):
    """Vmapped per-slot token draw, traced inside the fused decode step.

    logits: ``[B, V]`` over the REAL vocab (caller slices off padding).
    keys: ``[B, 2]`` uint32 per-request base keys (``PRNGKey(seed)``),
        part of the donated device carry.
    index: ``[B]`` i32 — the output-stream index of this draw (the
        engine's ``emitted`` counter), folded into the base key so token
        *t* of a request always sees the same noise.
    temperature/top_k/top_p: ``[B]`` per-slot parameter buffers.

    Rows with ``temperature <= 0`` take the plain ``argmax`` — the exact
    computation of the historical greedy engine, so greedy streams stay
    bit-identical. Non-greedy rows apply top-k then top-p truncation and
    draw via the Gumbel-argmax trick (an exact categorical sample).

    The rule: order the scaled logits descending, ties by vocab id (a
    stable sort); keep the first ``top_k`` (all when 0); of those, keep
    each token whose preceding softmax mass is below ``top_p``; draw the
    kept token with the largest scaled logit plus its Gumbel noise, the
    noise drawn in vocab order, ties to the lowest vocab id.
    """
    vocab = logits.shape[-1]
    # Materialize the logits ONCE before they fan out to the argmax and
    # sort consumers. Without the barrier XLA may duplicate the fused
    # logits computation per consumer with different last-bit rounding, so
    # two exactly-tied bf16 logits can sort one way and argmax the other —
    # the greedy branch then disagrees with a top_k=1 draw, and tie-breaks
    # stop being reproducible across program variants.
    logits = jax.lax.optimization_barrier(logits)

    def one(lg, key, idx, temp, k, p):
        greedy_tok = jnp.argmax(lg).astype(jnp.int32)
        scaled = lg.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
        g = jax.random.gumbel(jax.random.fold_in(key, idx), (vocab,))
        # one stable sort (descending logit order, the order of
        # ``argsort(-scaled)``) carries each entry's vocab id and noise
        # along, so truncation and draw stay in sorted order: an
        # element-wise gather over the vocab is slow on the TPU
        neg, ids, g_sorted = jax.lax.sort(
            (-scaled, jnp.arange(vocab, dtype=jnp.int32), g),
            num_keys=1, is_stable=True)
        k_eff = jnp.where(k > 0, k, vocab)
        keep_k = jnp.arange(vocab) < k_eff
        probs = jax.nn.softmax(jnp.where(keep_k, -neg, -jnp.inf))
        # keep tokens whose PRECEDING cumulative mass is < p: the top token
        # always survives, and the token that crosses p is included
        keep_p = (jnp.cumsum(probs) - probs) < p
        score = jnp.where(keep_k & keep_p, -neg, -jnp.inf) + g_sorted
        # the lowest vocab id among the best scores: argmax's tie-break
        # in vocab order, read by a masked reduction rather than a gather
        sampled = jnp.min(jnp.where(score == jnp.max(score), ids, vocab))
        return jnp.where(temp <= 0.0, greedy_tok, sampled)

    return jax.vmap(one)(logits, keys, index, temperature, top_k, top_p)
