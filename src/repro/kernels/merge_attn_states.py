"""Paper Kernel 1 — ``merge_attn_states_lse`` as a Pallas TPU kernel.

Semantics (paper Table 1):

    V_out = (e^{S_a} V_a + e^{S_b} V_b) / (e^{S_a} + e^{S_b})
    S_out = log(e^{S_a} + e^{S_b})

The CUDA optimization story (paper §5.3, Fig. 2) is **loop-invariant
hoisting**: the baseline recomputes the mixing weights (two exps, one
divide) for every element of the output vector; the optimized version
computes them once per output row. The TPU adaptation (DESIGN.md §2):

  * ``hoist`` — baseline (False) broadcasts the scores across the whole
    ``[rows, head_dim]`` tile and evaluates exp/divide *element-wise on the
    tile* (head_dim× more VPU transcendental work — the exact analogue of
    recomputing in the inner loop). The optimized variant (True) evaluates
    exp/reciprocal on the ``[rows, 1]`` score column only and broadcasts the
    two cheap scalars into the multiply-add.
  * ``use_reciprocal`` — ``inv = rcp(denom)`` then two multiplies, vs two
    divides (fast-math analogue of ``__frcp_rn``).
  * ``block_rows`` — VMEM tile height (grid sizing / occupancy analogue).
  * ``fuse_s_out`` — compute S_out in the same kernel instance (single HBM
    trip) vs a separate elementwise pass (baseline mirrors SGLang's fused
    form, so both default True; kept as an ablation knob).

Layout note: scores are carried as ``[rows, 1]`` fp32 columns. Mosaic pads
the lane dimension internally; the cost model charges that padding waste,
which is how the planning agent "sees" the layout pressure.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels._common import pad_rows, round_up, sublane_for
from repro.kernels.registry import (KernelSpace, Knob, TestCase,
                                    register_kernel_space)


@dataclasses.dataclass(frozen=True)
class MergeVariant:
    """Genome for merge_attn_states_lse (the space Astra searches)."""
    name: str = "baseline"
    block_rows: int = 16
    hoist: bool = False
    use_reciprocal: bool = False
    fuse_s_out: bool = True

    def describe(self) -> str:
        return (f"{self.name}: rows={self.block_rows} hoist={self.hoist} "
                f"rcp={self.use_reciprocal} fuse_s={self.fuse_s_out}")


# Literal-port baseline: one row-block per grid step (the CUDA kernel's
# one-thread-block-per-row structure) and per-element weight recompute.
BASELINE = MergeVariant()
OPTIMIZED = MergeVariant(
    name="astra_opt", block_rows=32, hoist=True, use_reciprocal=True)


def _weights(sa, sb, *, use_reciprocal):
    """LSE mixing weights + merged score. Shapes follow the inputs."""
    m = jnp.maximum(sa, sb)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    wa = jnp.exp(sa - m_safe)
    wb = jnp.exp(sb - m_safe)
    denom = wa + wb
    if use_reciprocal:
        inv = jnp.where(denom > 0, pl.reciprocal(denom, approx=False),
                        0.0)
    else:
        inv = jnp.where(denom > 0, 1.0 / denom, 0.0)
    return wa * inv, wb * inv, m + jnp.log(denom)


def _kernel(va_ref, sa_ref, vb_ref, sb_ref, vo_ref, so_ref, *,
            hoist, use_reciprocal, fuse_s_out):
    va = va_ref[...].astype(jnp.float32)
    vb = vb_ref[...].astype(jnp.float32)
    sa = sa_ref[...].astype(jnp.float32)   # [br, 1]
    sb = sb_ref[...].astype(jnp.float32)

    if hoist:
        # Optimized: weights computed once per row ([br, 1]), broadcast into
        # a lightweight multiply-add over the [br, head_dim] tile.
        a, b, s_out = _weights(sa, sb, use_reciprocal=use_reciprocal)
        vo = a * va + b * vb
    else:
        # Baseline: the CUDA inner loop recomputed exp/div per element; the
        # tile analogue evaluates the transcendentals on the broadcast
        # [br, head_dim] score tiles — head_dim× the VPU work.
        d = va.shape[-1]
        sa_t = jnp.broadcast_to(sa, (sa.shape[0], d))
        sb_t = jnp.broadcast_to(sb, (sb.shape[0], d))
        a_t, b_t, s_t = _weights(sa_t, sb_t, use_reciprocal=use_reciprocal)
        vo = a_t * va + b_t * vb
        s_out = s_t[:, :1]
    vo_ref[...] = vo.astype(vo_ref.dtype)
    if fuse_s_out:
        so_ref[...] = s_out.astype(so_ref.dtype)
    else:
        # Unfused ablation: S_out written by a separate pass; this instance
        # writes a placeholder that the second pass overwrites.
        so_ref[...] = jnp.zeros_like(so_ref)


def _s_out_kernel(sa_ref, sb_ref, so_ref):
    sa = sa_ref[...].astype(jnp.float32)
    sb = sb_ref[...].astype(jnp.float32)
    m = jnp.maximum(sa, sb)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    so = m + jnp.log(jnp.exp(sa - m_safe) + jnp.exp(sb - m_safe))
    so_ref[...] = so.astype(so_ref.dtype)


def merge_attn_states_lse(v_a: jax.Array, s_a: jax.Array,
                          v_b: jax.Array, s_b: jax.Array,
                          variant: MergeVariant = OPTIMIZED, *,
                          interpret: bool = False):
    """Merge two partial attention states. Returns ``(v_out, s_out)``.

    Accepts ``v: [..., head_dim]``, ``s: [...]`` (any leading shape, e.g.
    ``[seq, heads]``); computation runs on the flattened row view.
    """
    lead_shape = s_a.shape
    d = v_a.shape[-1]
    va2 = v_a.reshape(-1, d)
    vb2 = v_b.reshape(-1, d)
    sa2 = s_a.reshape(-1, 1).astype(jnp.float32)
    sb2 = s_b.reshape(-1, 1).astype(jnp.float32)
    n = va2.shape[0]

    sl = sublane_for(v_a.dtype)
    br = max(sl, (min(variant.block_rows, max(n, 1)) // sl) * sl) if n >= sl else max(n, 1)
    va2, n_pad = pad_rows(va2, br)
    vb2, _ = pad_rows(vb2, br)
    sa2, _ = pad_rows(sa2, br)
    sb2, _ = pad_rows(sb2, br)
    grid = (n_pad // br,)

    v_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    s_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))

    kern = functools.partial(_kernel, hoist=variant.hoist,
                             use_reciprocal=variant.use_reciprocal,
                             fuse_s_out=variant.fuse_s_out)
    v_out, s_out = pl.pallas_call(
        kern, grid=grid,
        in_specs=[v_spec, s_spec, v_spec, s_spec],
        out_specs=[v_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), v_a.dtype),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)],
        interpret=interpret,
    )(va2, sa2, vb2, sb2)

    if not variant.fuse_s_out:
        s_out = pl.pallas_call(
            _s_out_kernel, grid=grid,
            in_specs=[s_spec, s_spec],
            out_specs=s_spec,
            out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            interpret=interpret,
        )(sa2, sb2)

    v_out = v_out[:n].reshape(*lead_shape, d)
    s_out = s_out[:n, 0].reshape(lead_shape).astype(s_a.dtype)
    return v_out, s_out


def cost(variant: MergeVariant, *, rows: int, d: int, dtype):
    """Analytic v5e cost of this variant on ``v: [rows, d]``, ``s: [rows]``."""
    from repro.core import costmodel as cm

    item = jnp.dtype(dtype).itemsize
    sl = sublane_for(dtype)
    br = max(sl, (min(variant.block_rows, max(rows, 1)) // sl) * sl) \
        if rows >= sl else max(rows, 1)
    n_pad = round_up(rows, br)
    steps = n_pad // br
    ops = cm.OP

    # weight math: max, 2 exps, add, divide-or-rcp, 2 muls, log (s_out)
    weight_ops = (ops["max"] + 2 * ops["exp"] + ops["add"]
                  + (ops["rcp"] if variant.use_reciprocal else ops["div"])
                  + 2 * ops["mul"] + ops["log"] + 2 * ops["cmp"])
    mad_ops = 2 * ops["mul"] + ops["add"]  # a*va + b*vb
    cast = 3 * ops["cast"] if item < 4 else 0

    if variant.hoist:
        vpu = rows * (weight_ops + d * (mad_ops + cast))
    else:
        vpu = rows * d * (weight_ops + mad_ops + cast)

    # traffic: v_a, v_b read; v_out write; scores are narrow [rows,1] fp32
    # columns — charged with DMA-granule padding waste.
    v_bytes = 3 * rows * d * item
    s_logical, s_waste = cm.dma_bytes(3 * rows * 4, 4)
    pad_waste = (n_pad - rows) * d * item * 3

    main = cm.Cost(
        hbm_bytes=v_bytes + s_logical,
        vpu_ops=vpu,
        grid_steps=steps, n_calls=1,
        vmem_bytes=br * d * 3 * 4 + br * 128 * 3 * 4,
        align_waste_bytes=pad_waste + s_waste)
    costs = [main]
    if not variant.fuse_s_out:
        s2_logical, s2_waste = cm.dma_bytes(3 * rows * 4, 4)
        costs.append(cm.Cost(
            hbm_bytes=s2_logical, vpu_ops=rows * weight_ops,
            grid_steps=steps, n_calls=1, vmem_bytes=br * 128 * 3 * 4,
            align_waste_bytes=s2_waste))
    total = cm.combine(costs)
    total.validate()
    return total


reference = ref.merge_attn_states_lse


SUITE_SHAPES = ({"seq": 512, "heads": 32, "head_dim": 256},
                {"seq": 512, "heads": 40, "head_dim": 128},
                {"seq": 768, "heads": 32, "head_dim": 256},
                {"seq": 512, "heads": 64, "head_dim": 128},
                {"seq": 100, "heads": 7, "head_dim": 128})


def make_inputs(shape: dict, *, dtype=jnp.float32, seed: int = 0) -> TestCase:
    s, h, d = shape["seq"], shape["heads"], shape["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    va = jax.random.normal(ks[0], (s, h, d), dtype=dtype)
    vb = jax.random.normal(ks[1], (s, h, d), dtype=dtype)
    # scores with wide dynamic range + empty partitions (-inf)
    sa = jax.random.normal(ks[2], (s, h)) * 8.0
    sb = jax.random.normal(ks[3], (s, h)) * 8.0
    sb = jnp.where(jax.random.uniform(ks[4], (s, h)) < 0.05, -jnp.inf, sb)
    return TestCase(f"[{s},{h},{d}]", (va, sa, vb, sb),
                    {"rows": s * h, "d": d, "dtype": dtype})


def _run(variant, va, sa, vb, sb, *, interpret=True):
    return merge_attn_states_lse(va, sa, vb, sb, variant, interpret=interpret)


@register_kernel_space
def _space() -> KernelSpace:
    return KernelSpace(
        name="merge_attn_states_lse",
        baseline=BASELINE,
        default=OPTIMIZED,
        run=_run,
        oracle=reference,
        cost=cost,
        knobs=(
            Knob("block_rows", "pow2", 8, 2048, attacks=("overhead",)),
            Knob("hoist", "bool", attacks=("compute",), target=True,
                 note="hoist LSE weights out of the element dimension "
                      "(loop-invariant hoisting, paper Fig. 2)"),
            Knob("use_reciprocal", "bool", attacks=("compute",), target=True),
            Knob("fuse_s_out", "bool", attacks=("memory", "overhead"),
                 target=True,
                 note="compute S_out in the same pass"),
        ),
        suite_shapes=SUITE_SHAPES,
        make_inputs=make_inputs,
    )
