"""The served program for the Qwen2 / Qwen3 dense configurations: the
engine's model configuration, its weight tree, and the engine itself,
all from a configuration file. The weights are the reference's
(``qwen_reference``), laid out as the program holds them and made in one
jitted call on the device(s), in the dtype the configuration serves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import load_file

ref = load_file("models", "qwen_reference")

VOCAB_MULTIPLE = 256   # the program pads its vocabulary to this multiple


def program_config(cfg: dict):
    from repro.configs.base import ModelConfig
    arch = ref.Arch.of(cfg)
    if arch.head_dim * arch.heads != arch.d:
        raise ValueError("the program derives head_dim as d_model / heads")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=arch.layers,
        d_model=arch.d, n_heads=arch.heads, n_kv_heads=arch.kv_heads,
        d_ff=arch.d_ff, vocab=arch.vocab, qkv_bias=arch.bias,
        qk_norm=arch.qk_norm, rope_theta=arch.theta, norm_eps=arch.eps,
        dtype=cfg["torch_dtype"])


def _tree(key, arch: ref.Arch, dtype, vocab_pad: int) -> dict:
    """The program's weight tree (``repro.models.transformer.init``'s
    layout, gate/up unpermuted): matrices drawn straight in ``dtype``,
    norms and biases in float32, the vocabulary zero-padded to
    ``vocab_pad``."""
    st = ref.stacked_weights(key, arch, dtype)
    pad = vocab_pad - arch.vocab
    attn = {n: st[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv",
                               "q_norm", "k_norm") if n in st}
    return {
        "embed": jnp.pad(ref.global_leaf(key, arch, "embed", dtype),
                         ((0, pad), (0, 0))),
        "layers": {
            "attn": attn,
            "mlp": {"w_gateup": jnp.concatenate([st["w_gate"], st["w_up"]],
                                                -1),
                    "w_down": st["w_down"]},
            "attn_norm": st["attn_norm"], "mlp_norm": st["mlp_norm"]},
        "final_norm": ref.global_leaf(key, arch, "final_norm"),
        "lm_head": jnp.pad(ref.global_leaf(key, arch, "lm_head", dtype),
                           ((0, 0), (0, pad))),
    }


def make_weights(cfg: dict, seed: int, mesh=None, slots: int = 1):
    """The weights from ``seed``, in one jitted call, placed where the
    engine serves them (the mesh's shardings when ``mesh`` is given)."""
    arch = ref.Arch.of(cfg)
    dtype = jnp.dtype(cfg["serving"]["weights_dtype"])
    vocab_pad = -(-arch.vocab // VOCAB_MULTIPLE) * VOCAB_MULTIPLE
    key = ref.seed_key(seed)

    def build(key):
        return _tree(key, arch, dtype, vocab_pad)

    if mesh is None:
        return jax.jit(build)(key)
    from repro.sharding import tp
    plan = tp.make_plan(program_config(cfg), mesh, slots)
    shardings = tp.param_shardings(jax.eval_shape(build, key), plan)
    return jax.jit(build, out_shardings=shardings)(key)


def make_engine(cfg: dict, params, mesh=None):
    """The serving engine at the configuration's sizes (FCFS, paged pool,
    prefix cache as configured)."""
    from repro.serving import CacheConfig, Engine
    s = cfg["serving"]
    return Engine(params, program_config(cfg), slots=s["slots"],
                  max_seq=s["max_seq"], mesh=mesh,
                  cache_manager=CacheConfig(paged=True,
                                            page_size=s["page_size"],
                                            prefix_cache=s["prefix_cache"]))
