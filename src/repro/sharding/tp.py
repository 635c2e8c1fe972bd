"""Tensor-parallel serving plan over the ``(data, model)`` mesh.

The serving engine's donated programs (decode step, prefill admission,
swap-restore, CoW page copy) are wrapped in ``shard_map`` over the mesh
from ``launch/mesh.py``; this module holds everything those wrapped
bodies need:

* ``Plan`` / ``make_plan`` — which logical axes actually shard, resolved
  through the divisibility-gated rules in :mod:`repro.sharding.rules`
  (``heads``/``kv_heads``/``mlp``/``vocab`` over ``model``; the slot
  batch over ``data``). Non-divisible head counts fall back to
  replicated heads with the MLP/vocab axes still sharded — the rules'
  documented fallback, exercised by qwen2's 2 smoke / 14 full heads.
* ``shard_params`` / ``param_specs`` / ``kv_spec`` / ``make_cache`` —
  physical placement of the dense-family weight tree and the paged KV
  pool (built in its sharding, never whole on one device). The fused
  gate/up projection is column-pre-permuted (``permute_gateup``) so each
  model shard holds its own ``(gate_m, up_m)`` pair and
  ``silu_and_mul`` splits locally. ``param_shardings`` lets an
  initializer build the (unpermuted) weights straight into this layout
  (``repro.launch.serve.init_params``) when one device cannot hold them.
* ``active`` / ``current`` — a trace-time context: the engine enters
  the plan inside the ``shard_map`` body, so the *unchanged* model code
  in :mod:`repro.models` sees it while tracing and routes through the
  gather helpers below. With no active plan every helper is the
  identity, so single-device jaxprs are byte-identical to before.
* ``gather_heads`` / ``gather_mlp`` / ``gather_vocab`` /
  ``gather_data`` / ``data_shard`` — the collective hooks, each gather
  under a ``jax.named_scope`` of its own name (``tp.gather_heads``, ...)
  so it can be found in HLO metadata and traces. Every
  cross-device exchange is an **all-gather** (never a psum): partial
  results are concatenated, not summed, so the sharded computation is
  bitwise identical to the single-device one in the engine's bf16
  compute dtype (asserted end-to-end by ``tools/sharded_check.py``).
  The split-KV LSE-merge path in ``kernels/flash_decode.py`` stays the
  contiguous-cache ``shard_map``/pmap alternative; its psum combiner is
  not bit-exact, which is why the paged serving plan shards heads, not
  ``kv_seq``.

See ``docs/ARCHITECTURE.md`` (Sharded serving) for the full design,
including the per-arch divisibility table.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import _resolve


@dataclasses.dataclass(frozen=True)
class Plan:
    """Resolved sharding plan for one engine instance.

    ``heads``/``mlp``/``vocab`` say whether that logical axis shards
    over ``model``; ``batch`` whether the slot axis shards over
    ``data``. All False degenerates to fully replicated execution
    (still correct, still bit-identical)."""

    mesh: Mesh
    data: int
    model: int
    heads: bool
    mlp: bool
    vocab: bool
    batch: bool

    def describe(self) -> dict:
        """Stats-friendly summary (surfaced by ``Engine.stats()``)."""
        return {"data": self.data, "model": self.model,
                "heads_tp": self.heads, "mlp_tp": self.mlp,
                "vocab_tp": self.vocab, "batch_dp": self.batch}


def make_plan(cfg, mesh: Mesh, slots: int) -> Plan:
    """Resolve ``cfg``'s logical axes against ``mesh`` via the rules.

    Heads shard only when *both* ``n_heads`` and ``n_kv_heads`` divide
    the model axis: the GQA query groups are kv-major, so a contiguous
    query-head shard lines up with its kv-head shard — one without the
    other would split groups. MLP/vocab resolve independently (the
    documented replicated-heads fallback). The slot batch shards over
    ``data`` when it divides; weights and the KV pool stay replicated
    over ``data`` — serving has no gradient reduce, so FSDP's
    ``embed``→``data`` rule is deliberately not applied here.
    """
    if cfg.family != "dense":
        raise ValueError(
            f"mesh serving supports the dense family only (got "
            f"{cfg.family!r}: per-slot-coupled or stateful decode)")
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "model" not in axis_sizes or "data" not in axis_sizes:
        raise ValueError(f"mesh must carry ('data', 'model') axes, got "
                         f"{mesh.axis_names}")
    data, model = axis_sizes["data"], axis_sizes["model"]
    heads = (_resolve("heads", cfg.n_heads, mesh) == "model"
             and _resolve("kv_heads", cfg.n_kv_heads, mesh) == "model")
    mlp = _resolve("mlp", cfg.d_ff, mesh) == "model"
    vocab = _resolve("vocab", cfg.padded_vocab, mesh) == "model"
    batch = data > 1 and slots % data == 0
    return Plan(mesh=mesh, data=data, model=model, heads=heads,
                mlp=mlp, vocab=vocab, batch=batch)


# ---------------------------------------------------------------------------
# physical placement
# ---------------------------------------------------------------------------

def param_specs(params: dict, plan: Plan) -> dict:
    """PartitionSpec tree for the dense-family weight layout.

    Matches the serving collectives: sharded projections produce local
    partials that are all-gathered *before* the replicated consumer
    (``wo``, ``w_down``), so those stay replicated. The embedding is
    replicated too — the token gather must be device-local.
    """
    h = "model" if plan.heads else None
    attn = {"wq": P(None, None, h, None), "wk": P(None, None, h, None),
            "wv": P(None, None, h, None), "wo": P()}
    attn_tree = params["layers"]["attn"]
    if "bq" in attn_tree:
        attn["bq"] = P(None, h, None)
        attn["bk"] = P(None, h, None)
        attn["bv"] = P(None, h, None)
    if "q_norm" in attn_tree:
        attn["q_norm"] = P()
        attn["k_norm"] = P()
    mlp = {"w_gateup": P(None, None, "model" if plan.mlp else None),
           "w_down": P()}
    return {"embed": P(),
            "layers": {"attn": attn, "mlp": mlp,
                       "attn_norm": P(), "mlp_norm": P()},
            "final_norm": P(),
            "lm_head": P(None, "model" if plan.vocab else None)}


def kv_spec(plan: Plan) -> P:
    """Spec for any KV tensor whose axis 3 is ``kv_heads`` — the paged
    pool ``[L, pages, page, Hkv, dh]``, gathered page reads, and the
    contiguous swap payload ``[L, B, S, Hkv, dh]`` all share it.
    (``rules.spec_for`` can't be used for the contiguous layout: its
    one-axis-per-mesh-axis dedup would hand ``model`` to ``kv_seq``
    first; the serving plan shards heads, never ``kv_seq``.) Trailing
    ``None`` entries are dropped — shard_map outputs carry the
    normalized spec, and the pool ``make_cache`` builds must carry the
    *same* sharding or donated round-trips retrace."""
    return P(None, None, None, "model") if plan.heads else P()


def kv_specs(plan: Plan) -> dict:
    """``{"k", "v"}`` spec tree matching the cache pytrees."""
    s = kv_spec(plan)
    return {"k": s, "v": s}


def _shardings(tree, specs, mesh: Mesh):
    """``NamedSharding`` tree matching ``tree`` (which may hold
    ``ShapeDtypeStruct``s) from a PartitionSpec tree — specs are tuples,
    hence the flatten_up_to dance, as in ``rules.tree_shardings``."""
    flat, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, [
        NamedSharding(mesh, s) for s in treedef.flatten_up_to(specs)])


def permute_gateup(wg, cfg, plan: Plan):
    """Put ``(gate_m, up_m)`` on model shard m.

    ``w_gateup [.., D, 2F]`` fuses gate columns ``[0, F)`` and up columns
    ``[F, 2F)``; naive column sharding would hand shard 0 gate-only
    columns. The new order, ``[.., (gate|up), model, F/model] -> [..,
    model, (gate|up), F/model]``, is pure column movement, so gathering
    the per-shard ``silu_and_mul`` outputs restores the original column
    order bitwise (``gather_mlp``). As a reshape/transpose it partitions
    as an all-to-all, not a gather of the whole matrix onto one device."""
    lead = wg.shape[:-1]
    wg = wg.reshape(*lead, 2, plan.model, cfg.d_ff // plan.model)
    return jax.numpy.swapaxes(wg, -3, -2).reshape(*lead, 2 * cfg.d_ff)


def param_shardings(params: dict, plan: Plan) -> dict:
    """``NamedSharding`` tree of ``param_specs``."""
    return _shardings(params, param_specs(params, plan), plan.mesh)


def shard_params(params: dict, cfg, plan: Plan) -> dict:
    """Place an unpermuted weight tree on the mesh per ``param_specs``,
    then permute the fused gate/up columns there when the MLP axis
    shards: one jitted program over the placed matrix, so no device holds
    the whole of it. It maps over the stacked layer axis, so the
    exchange's buffers hold one layer's columns, not every layer's: for
    qwen3-8b on four chips, 0.09 GiB of temporaries a chip in place of
    3.4. The caller's tree is left as it was."""
    shardings = param_shardings(params, plan)
    placed = jax.device_put(params, shardings)
    if not plan.mlp:
        return placed
    mlp = placed["layers"]["mlp"]
    permute = functools.partial(permute_gateup, cfg=cfg, plan=plan)
    wg = jax.jit(lambda w: lax.map(permute, w),
                 out_shardings=shardings["layers"]["mlp"]["w_gateup"])(
                     mlp["w_gateup"])
    layers = dict(placed["layers"], mlp=dict(mlp, w_gateup=wg))
    return dict(placed, layers=layers)


def make_cache(init, plan: Plan):
    """Build a fresh KV cache pytree straight into its mesh placement:
    ``init`` (the cache manager's zero-fill) runs as one jitted program
    whose ``out_shardings`` are ``kv_specs``, so each device fills only
    its own shard and none ever holds the whole pool. Those shardings are
    the ones the wrapped programs' outputs carry, so donated round-trips
    do not retrace."""
    shardings = _shardings(jax.eval_shape(init), kv_specs(plan), plan.mesh)
    return jax.jit(init, out_shardings=shardings)()


def replicate(x, plan: Plan):
    """Place a carry buffer fully replicated on the mesh (required so
    donated carries round-trip with a consistent committed sharding)."""
    return jax.device_put(x, NamedSharding(plan.mesh, P()))


# ---------------------------------------------------------------------------
# trace-time plan context + collective hooks
# ---------------------------------------------------------------------------

_ACTIVE: Plan | None = None


@contextlib.contextmanager
def active(plan: Plan):
    """Make ``plan`` visible to the model code being traced. Entered
    *inside* the shard_map body (i.e. during jit tracing), so the hooks
    below run with the mesh axes in scope."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, plan
    try:
        yield
    finally:
        _ACTIVE = prev


def current() -> Plan | None:
    """The plan being traced under, or None (single-device paths)."""
    return _ACTIVE


def gather_heads(o):
    """All-gather attention outputs ``[B, S, H_local, dh]`` over
    ``model`` before the replicated ``wo`` contraction. Identity when
    heads are replicated (fallback) or no plan is active."""
    p = _ACTIVE
    if p is None or not p.heads:
        return o
    with jax.named_scope("tp.gather_heads"):
        return lax.all_gather(o, "model", axis=2, tiled=True)


def gather_mlp(h):
    """All-gather ``silu_and_mul`` outputs ``[..., F_local]`` over
    ``model`` before the replicated down projection. The gate/up column
    pre-permutation makes this concatenation restore the original
    column order exactly."""
    p = _ACTIVE
    if p is None or not p.mlp:
        return h
    with jax.named_scope("tp.gather_mlp"):
        return lax.all_gather(h, "model", axis=h.ndim - 1, tiled=True)


def gather_vocab(logits):
    """All-gather vocab-sharded logits ``[..., V_local]`` over
    ``model`` — argmax/sampling and the ``[:, :vocab]`` slice need the
    full (padded) vocabulary in original order."""
    p = _ACTIVE
    if p is None or not p.vocab:
        return logits
    with jax.named_scope("tp.gather_vocab"):
        return lax.all_gather(logits, "model", axis=logits.ndim - 1,
                              tiled=True)


def data_shard(x, axis: int = 0):
    """Slice the slot-batch axis down to this data shard's rows.
    Identity when the batch is replicated over ``data`` (non-divisible
    slot count, data=1, prefill's batch of one, or no plan)."""
    p = _ACTIVE
    if p is None or not p.batch or x.shape[axis] % p.data != 0:
        return x
    shard = x.shape[axis] // p.data
    return lax.dynamic_slice_in_dim(
        x, lax.axis_index("data") * shard, shard, axis=axis)


def gather_data(x, axis: int = 0):
    """All-gather ``data``-sharded per-slot values back to the full
    slot axis (the decode step's single cross-``data`` exchange: the
    new KV rows for the replicated pool write, and the per-slot token).
    Identity when the batch is replicated over ``data``."""
    p = _ACTIVE
    if p is None or not p.batch:
        return x
    with jax.named_scope("tp.gather_data"):
        return lax.all_gather(x, "data", axis=axis, tiled=True)


def wrap(plan: Plan, fn, in_specs, out_specs, donate_argnums=()):
    """``jit(shard_map(fn))`` with the plan entered inside the body.

    ``check_vma=False`` everywhere: replicated ``P()`` outputs are
    genuinely identical on every device (they are all-gather results or
    elementwise functions of replicated inputs), but shard_map's
    varying-axes checker cannot see through the gather pattern."""
    def body(*args):
        with active(plan):
            return fn(*args)

    sm = jax.shard_map(body, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(sm, donate_argnums=donate_argnums)
