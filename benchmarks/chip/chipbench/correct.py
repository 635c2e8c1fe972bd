"""The comparison that decides ``correct`` for a served model.

Once the window has closed, two samples of the requests the program
finished are drawn from the seed, each always holding the longest
finished one: one of greedy requests, and one of requests that sample
(topped up, where they hold too few tokens, with the tokens served so far
to requests of their kind still running). Each is run through the plain
reference over each prompt and its served tokens.

Each served token is scored against the reference's choice at its
position. A greedy token's score is the reference's logit; a sampled
token's is the reference's logit over the request's temperature plus the
Gumbel noise of the request's draw (``fold_in(PRNGKey(sample_seed), t)``
for its ``t``-th output token), so that the reference replays the same
draw exactly. The number read for a token is the gap by which its score
lies below the best score at its position, and the number compared is
the widest gap of the sample. The control puts a lower-precision
reference in the program's place and reads the same gap for the token it
puts first under the same noise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 128           # rows scored at once (one compiled shape)


def select(records, rng, min_tokens: int, max_requests: int,
           greedy: bool = True) -> list:
    """Finished requests of one kind (greedy, or sampled): the one with
    most served tokens first, then others in the seed's order, until
    ``min_tokens`` served tokens or ``max_requests`` requests. Where the
    finished ones hold fewer tokens, requests of that kind still being
    served add the tokens they have been served so far, in the seed's
    order."""
    def key(r):
        return (-len(r.tokens), -r.prompt_len, r.index)

    done = sorted((r for r in records if r.greedy == greedy and r.tokens
                   and r.finish_reason == "done"), key=key)
    live = sorted((r for r in records if r.greedy == greedy
                   and len(r.tokens) > 1 and r.finish_reason is None),
                  key=key)
    out = done[:1]
    for pool in (done[1:], live):
        for i in rng.permutation(len(pool)):
            if sum(len(r.tokens) for r in out) >= min_tokens \
                    or len(out) >= max_requests:
                return out
            out.append(pool[i])
    return out


def positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Rows whose logits choose served token ``t`` (row ``prompt_len - 1 +
    t``), in a sequence of the prompt and all served tokens but the
    last."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def gaps(scores: np.ndarray, chosen) -> np.ndarray:
    """How far below the best score each chosen token's score lies."""
    chosen = np.asarray(chosen)
    return scores.max(-1) - scores[np.arange(len(chosen)), chosen]


@jax.jit
def _perturb(logits, key, index, temperature):
    def noise(i):
        return jax.random.gumbel(jax.random.fold_in(key, i),
                                 logits.shape[-1:])
    return logits / temperature + jax.vmap(noise)(index)


def draw_scores(logits: np.ndarray, temperature: float,
                sample_seed: int) -> np.ndarray:
    """The scores of a sampled request's draws: ``logits`` (one row per
    output token, in order) over the temperature plus each token's
    Gumbel noise; the draw takes the best."""
    key = jax.random.PRNGKey(sample_seed)
    m, vocab = logits.shape
    out = np.empty((m, vocab), np.float32)
    for lo in range(0, m, CHUNK):
        rows = logits[lo:lo + CHUNK]
        pad = np.zeros((CHUNK, vocab), np.float32)
        pad[:len(rows)] = rows
        got = _perturb(jnp.asarray(pad), key,
                       jnp.arange(lo, lo + CHUNK, dtype=jnp.int32),
                       jnp.float32(temperature))
        out[lo:lo + len(rows)] = np.asarray(got)[:len(rows)]
    return out


def widest_gap(sample, reference, control=None) -> tuple:
    """(widest gap of the served tokens, widest gap of the control's first
    choices or None, tokens compared)."""
    served_gap, control_gap, n = 0.0, None, 0
    for r in sample:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        rows = positions(r.prompt_len, len(r.tokens))

        def scores(model):
            lg = model.logits(seq, rows)
            return lg if r.greedy else \
                draw_scores(lg, r.temperature, r.sample_seed)

        ref = scores(reference)
        served_gap = max(served_gap, float(gaps(ref, r.tokens).max()))
        if control is not None:
            g = float(gaps(ref, scores(control).argmax(-1)).max())
            control_gap = g if control_gap is None else max(control_gap, g)
        n += len(r.tokens)
    return served_gap, control_gap, n
