"""Shared helpers for the Pallas kernels: padding, tiling, alignment."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# TPU register-tile geometry: the VPU operates on (sublane, lane) = (8, 128)
# fp32 tiles ((16, 128) for bf16). Block shapes should be multiples of these
# or Mosaic pads them internally (wasting lanes); the cost model charges for
# that waste, and the Astra planning agent learns to avoid it.
SUBLANE = 8
LANE = 128


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def sublane_for(dtype) -> int:
    """Minimum sublane multiple for a dtype (fp32: 8, bf16: 16, int8/fp8: 32)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def pad_rows(x: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    """Pad the leading dim of a 2-D array up to a multiple of block_rows."""
    n = x.shape[0]
    n_pad = round_up(n, block_rows)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1))
    return x, n_pad


def pick_block_rows(n_rows: int, row_bytes: int, *, vmem_budget: int = 8 * 2**20,
                    max_rows: int = 256, dtype=jnp.float32) -> int:
    """Pick a row-block size: as many rows as fit the VMEM budget, aligned."""
    sl = sublane_for(dtype)
    rows = max(sl, min(max_rows, vmem_budget // max(row_bytes, 1)))
    rows = max(sl, (rows // sl) * sl)
    return min(rows, round_up(n_rows, sl))
