"""Plain reference of the Qwen2 / Qwen3 dense decoder, and the weights
both it and the served program use, made from a seed.

Follows the published description (Qwen2 technical report,
arXiv:2407.10671; the Qwen3 model card and config): pre-norm blocks with
RMSNorm, grouped-query attention with rotary embeddings (``rotate_half``
form, ``inv_freq = theta ** (-2i / head_dim)``), q/k/v biases (Qwen2) or
RMSNorm of each query and key head before the rotation (Qwen3), and a
SwiGLU MLP. Plain ``jax.numpy`` in float32 at ``highest`` matmul
precision, the whole sequence at once, one layer at a time with that
layer's weights made when it runs. It imports nothing of the program.

Departures, both as the program serves: the output head is a matrix of
its own (``tie_word_embeddings`` false; see the configuration file), and
the weights are random. Each weight is an int8 draw times a power of two
(about the fan-in scale), so it is exact in bfloat16 and float32 alike,
and the program's copy and this one hold the same numbers.

``precision="fp8"`` is the control: the same computation with every
matrix and every matmul input rounded to float8 (e4m3, scaled per tensor
for weights and per row for activations), the step below the bfloat16
that the configuration states.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3, "wq": 10, "wk": 11,
            "wv": 12, "wo": 13, "bq": 14, "bk": 15, "bv": 16, "q_norm": 17,
            "k_norm": 18, "w_gate": 20, "w_up": 21, "w_down": 22,
            "attn_norm": 23, "mlp_norm": 24}
FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    bias: bool
    qk_norm: bool
    theta: float
    eps: float

    @classmethod
    def of(cls, cfg: dict) -> "Arch":
        heads = cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=heads, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim")
                   or cfg["hidden_size"] // heads,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   bias=cfg["model_type"] == "qwen2",
                   qk_norm=cfg["model_type"] == "qwen3",
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]))

    def layer_leaves(self) -> dict:
        """name -> (shape of one layer's leaf, kind, fan-in)."""
        d, h, kv, dh, f = (self.d, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        out = {"wq": ((d, h, dh), "matrix", d),
               "wk": ((d, kv, dh), "matrix", d),
               "wv": ((d, kv, dh), "matrix", d),
               "wo": ((h, dh, d), "matrix", h * dh),
               "w_gate": ((d, f), "matrix", d),
               "w_up": ((d, f), "matrix", d),
               "w_down": ((f, d), "matrix", f),
               "attn_norm": ((d,), "norm", 0),
               "mlp_norm": ((d,), "norm", 0)}
        if self.bias:
            out.update(bq=((h, dh), "bias", 0), bk=((kv, dh), "bias", 0),
                       bv=((kv, dh), "bias", 0))
        if self.qk_norm:
            out.update(q_norm=((dh,), "norm", 0), k_norm=((dh,), "norm", 0))
        return out


def seed_key(seed: int):
    """A raw threefry key from any whole number below 2**64."""
    return jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     jnp.uint32)


def draw(key, name: str, layer, shape, kind: str, fan_in: int, dtype):
    """One leaf: uniform int8 bits times a power of two."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    v = jax.lax.bitcast_convert_type(
        jax.random.bits(k, shape, jnp.uint8), jnp.int8).astype(jnp.float32)
    if kind == "matrix":     # about U(+-sqrt(3 / fan_in)): std fan_in**-0.5
        v = v * 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in) / 128))
    elif kind == "embed":
        v = v * 2.0 ** -7
    elif kind == "norm":
        v = 1.0 + v * 2.0 ** -10
    else:                    # bias
        v = v * 2.0 ** -10
    return v.astype(dtype)


def global_leaf(key, arch: Arch, name: str, dtype=jnp.float32):
    """``embed`` [V, D], ``lm_head`` [D, V] or ``final_norm`` [D]."""
    spec = {"embed": ((arch.vocab, arch.d), "embed", 0),
            "lm_head": ((arch.d, arch.vocab), "matrix", arch.d),
            "final_norm": ((arch.d,), "norm", 0)}[name]
    return draw(key, name, 0, *spec, dtype)


def layer_weights(key, arch: Arch, layer, matrix_dtype=jnp.float32) -> dict:
    """One layer's leaves (``layer`` may be traced): matrices in
    ``matrix_dtype``, norms and biases in float32."""
    return {n: draw(key, n, layer, shape, kind, fan,
                    matrix_dtype if kind == "matrix" else jnp.float32)
            for n, (shape, kind, fan) in arch.layer_leaves().items()}


def stacked_weights(key, arch: Arch, matrix_dtype) -> dict:
    """Every layer's leaves stacked on axis 0 (vmap of ``layer_weights``,
    so layer ``l`` holds exactly what ``layer_weights(key, arch, l)``
    gives)."""
    return jax.vmap(lambda l: layer_weights(key, arch, l, matrix_dtype))(
        jnp.arange(arch.layers))


# -- the forward pass ---------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 with a scale that maps the largest magnitude
    (per tensor, or per row along ``axis``) to the format's largest."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, dh] at positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, arch: Arch, fp8: bool):
    """One pre-norm block over the residual stream ``x`` [S, D]."""
    act = (lambda a: _q8(a, -1)) if fp8 else (lambda a: a)
    wt = (lambda m: _q8(m, None)) if fp8 else (lambda m: m)
    h = act(_rms(x, w["attn_norm"], arch.eps))
    q = jnp.einsum("sd,dhk->shk", h, wt(w["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, wt(w["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, wt(w["wv"]))
    if arch.bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if arch.qk_norm:
        q = _rms(q, w["q_norm"], arch.eps)
        k = _rms(k, w["k_norm"], arch.eps)
    q, k = _rope(q, arch.theta), _rope(k, arch.theta)
    q, k, v = act(q), act(k), act(v)
    group = arch.heads // arch.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = x.shape[0]
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(arch.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("qhk,hkd->qd", act(o), wt(w["wo"]))
    h = act(_rms(x, w["mlp_norm"], arch.eps))
    g = h @ wt(w["w_gate"])
    u = h @ wt(w["w_up"])
    return x + act(jax.nn.silu(g) * u) @ wt(w["w_down"])


class Reference:
    """Logits of the reference over one sequence, at chosen positions."""

    def __init__(self, cfg: dict, seed: int, precision: str = "f32"):
        self.arch = Arch.of(cfg)
        self.key = seed_key(seed)
        self.fp8 = precision == "fp8"
        arch, fp8 = self.arch, self.fp8
        self._layer_w = jax.jit(lambda key, l: layer_weights(key, arch, l))
        self._block = jax.jit(lambda x, w: _block(x, w, arch, fp8))

        @jax.jit
        def head(x, rows, key):
            h = _rms(x[rows], global_leaf(key, arch, "final_norm"), arch.eps)
            w = global_leaf(key, arch, "lm_head")
            if fp8:
                h, w = _q8(h, -1), _q8(w, None)
            return h @ w

        self._head = head
        self._embed = jax.jit(
            lambda key, toks: global_leaf(key, arch, "embed")[toks])

    def logits(self, tokens, rows) -> np.ndarray:
        """float32 logits [len(rows), vocab] after ``tokens[:row + 1]``
        for each of ``rows``. The sequence and the rows are padded to
        powers of two, so few programs compile (causal: pad positions
        never reach earlier ones; pad rows are dropped)."""
        n, m = len(tokens), len(rows)
        toks = np.zeros(_pow2(n, 256), np.int32)
        toks[:n] = tokens
        idx = np.zeros(_pow2(m, 64), np.int32)
        idx[:m] = rows
        with jax.default_matmul_precision("highest"):
            x = self._embed(self.key, jnp.asarray(toks))
            for l in range(self.arch.layers):
                x = self._block(x, self._layer_w(self.key, l))
            out = self._head(x, jnp.asarray(idx), self.key)
        return np.asarray(out, np.float32)[:m]


def _pow2(n: int, least: int) -> int:
    b = least
    while b < n:
        b *= 2
    return b
