"""Fused filter + project + grouped-aggregate Pallas TPU kernel.

This is the hot loop of every set-oriented plan Froid produces (the paper's
TPC-H experiments bottom out in exactly this op), adapted to the TPU:

* hash tables are a poor fit for the MXU/VPU, so grouping is done as
  **one-hot × matmul partial aggregation**: for a VMEM tile of rows, build
  the (groups × rows) one-hot matrix of group ids, then
  ``onehot @ values.T`` on the MXU accumulates per-group sums for the whole
  tile in one systolic pass;
* the row stream is tiled through VMEM lane-major: group ids arrive as a
  ``(1, block_rows)`` int32 row and values as a ``(n_aggs + 1, block_rows)``
  block, so neither needs a relayout in the kernel; the accumulator
  ``(groups, n_aggs + 1)`` lives in the output block, which stays resident
  in VMEM across the sequential grid.

The fused filter is folded into the group ids before the call (a filtered
row gets id −1 and matches no group), and count aggregation falls out of
the same matmul: the wrapper appends a row of ones to the value matrix.

The dot runs at ``Precision.HIGHEST``: the one-hot side is exact in any
precision, and the value side then keeps f32 accuracy on the MXU instead of
being rounded to bf16 (which would put ~1e-3 relative error on every sum).

VMEM budget: the one-hot tile is ``groups × block_rows`` f32.
``block_rows`` is chosen so that tile stays near ``ONEHOT_ELEMS`` elements
(2 MiB); ``MAX_GROUPS`` bounds the group count, so the smallest tile is
``MAX_GROUPS × MIN_BLOCK_ROWS`` = 2 MiB as well.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: largest group count the kernel accepts (callers fall back above it)
MAX_GROUPS = 2048
#: target one-hot tile size, in elements
ONEHOT_ELEMS = 1 << 19
MIN_BLOCK_ROWS = 256
MAX_BLOCK_ROWS = 8192


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pick_block_rows(num_groups: int, n: int) -> int:
    """Rows per grid step: as many as keep the one-hot tile near
    ``ONEHOT_ELEMS``, a power of two in [MIN_BLOCK_ROWS, MAX_BLOCK_ROWS],
    and no more than the (128-aligned) row count needs."""
    gp = _round_up(max(num_groups, 1), 8)
    rows = MAX_BLOCK_ROWS
    while rows > MIN_BLOCK_ROWS and gp * rows > ONEHOT_ELEMS:
        rows //= 2
    return max(128, min(rows, _round_up(n, 128)))


def _relagg_kernel(gid_ref, vals_ref, out_ref):
    """Grid: (num_row_tiles,).  out_ref block: (groups_padded, n_aggs+1)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...]  # (1, block_rows) int32, -1 = filtered out
    groups = jax.lax.broadcasted_iota(
        jnp.int32, (out_ref.shape[0], gid.shape[1]), 0)
    onehot = (groups == gid).astype(jnp.float32)  # (G, block_rows)
    # (G, rows) @ (n_aggs+1, rows).T on the MXU
    out_ref[...] += jax.lax.dot_general(
        onehot,
        vals_ref[...],
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def relagg_pallas(
    gid: jnp.ndarray,  # (n,) int32 group ids in [0, num_groups)
    mask: jnp.ndarray,  # (n,) bool
    vals: jnp.ndarray,  # (n, n_aggs) f32
    num_groups: int,
    block_rows: int | None = None,
    interpret: bool = False,
):
    if not 1 <= num_groups <= MAX_GROUPS:
        raise ValueError(
            f"relagg takes 1..{MAX_GROUPS} groups, got {num_groups}")
    n, n_aggs = vals.shape
    if block_rows is None:
        block_rows = pick_block_rows(num_groups, n)
    n_pad = (-n) % block_rows
    gp = _round_up(num_groups, 8)
    # filter folded into the ids; padding rows are filtered out too
    gid_row = jnp.pad(jnp.where(mask, gid.astype(jnp.int32), -1), (0, n_pad),
                      constant_values=-1)[None, :]
    vals_t = jnp.pad(
        jnp.concatenate([vals.astype(jnp.float32).T,
                         jnp.ones((1, n), jnp.float32)], axis=0),
        ((0, 0), (0, n_pad)))
    tiles = (n + n_pad) // block_rows

    out = pl.pallas_call(
        _relagg_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda t: (0, t)),
            pl.BlockSpec((n_aggs + 1, block_rows), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((gp, n_aggs + 1), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((gp, n_aggs + 1), jnp.float32),
        interpret=interpret,
        name="relagg",
    )(gid_row, vals_t)
    return out[:num_groups, :n_aggs], out[:num_groups, n_aggs]  # (sums, counts)
