"""Operations and bytes of the work a cell asks for, from the
configuration's widths, and the chip's peaks.

Counts are of the work, not of how the program does it: weights at the
compute dtype (never the storage dtype), live KV rows (never the whole
pool or page padding), the vocabulary as published (never padded), and
an ideal split over the chips. A program that stores bf16 weights, walks
only live pages or shards more matrices then raises a share honestly.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """``{"bf16_flop_s", "hbm_bytes_s"}`` of one chip of
    ``device_kind``; a kind that is not in the table is an error."""
    table = json.loads(PEAKS.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The widths the counts need, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(
            layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or
            cfg["hidden_size"] // cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            tied=bool(cfg.get("tie_word_embeddings", False)),
            dtype_bytes=DTYPE_BYTES[cfg["torch_dtype"]])

    # -- parameters -----------------------------------------------------------
    @property
    def layer_params(self) -> int:
        """Matrix parameters of one block: q, k, v, o and the SwiGLU."""
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def embed_params(self) -> int:
        return 0 if self.tied else self.vocab * self.d_model

    @property
    def matmul_params(self) -> int:
        """Parameters every token multiplies by: the blocks and the head."""
        return self.layers * self.layer_params + self.head_params

    @property
    def weight_params(self) -> int:
        """Matrix and embedding parameters (norms and biases left out)."""
        return self.matmul_params + self.embed_params

    @property
    def weight_bytes(self) -> int:
        return self.weight_params * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.dtype_bytes

    # -- work -----------------------------------------------------------------
    def attn_flops(self, kv_len: int) -> int:
        """Scores and weighted sum of one query over ``kv_len`` keys, all
        layers: 2 multiply-adds per (head, dim, key)."""
        return 4 * self.layers * self.heads * self.head_dim * kv_len

    def prefill_flops(self, n: int, start: int = 0) -> int:
        """Positions ``start..n-1`` of an ``n``-token prompt (those before
        ``start`` already cached): every block for each, causal attention,
        and the head for the last position only."""
        blocks = 2 * self.layers * self.layer_params * (n - start)
        keys = n * (n + 1) // 2 - start * (start + 1) // 2
        attn = 4 * self.layers * self.heads * self.head_dim * keys
        return blocks + attn + 2 * self.head_params

    def decode_flops(self, kv_lens) -> int:
        """One decode step over slots whose queries see ``kv_lens`` keys."""
        return sum(2 * self.matmul_params + self.attn_flops(n)
                   for n in kv_lens)

    def attn_bytes(self, kv_lens) -> int:
        """What the decode attention must move in one step: each slot's
        live K and V rows and its q and output rows, all layers."""
        per_row = 2 * self.kv_heads * self.head_dim * self.dtype_bytes
        qo = 2 * self.heads * self.head_dim * self.dtype_bytes
        return self.layers * sum(n * per_row + qo for n in kv_lens)

    def decode_bytes(self, kv_lens) -> int:
        """One decode step: every matrix once, the embedding rows of the
        batch, the new KV rows written, and the attention's bytes."""
        b = len(kv_lens)
        return (self.matmul_params * self.dtype_bytes
                + b * self.d_model * self.dtype_bytes
                + b * self.kv_bytes_per_token
                + self.attn_bytes(kv_lens))


def least_seconds(flops: float, nbytes: float, peak: dict,
                  chips: int = 1) -> float:
    """The least time ``chips`` chips could take for the work: the larger
    of its compute bound and its memory bound, split ideally."""
    return max(flops / (chips * peak["bf16_flop_s"]),
               nbytes / (chips * peak["hbm_bytes_s"]))
