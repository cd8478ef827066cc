"""Fused threshold-selection kernel — the streaming emission hot loop.

Selection emission is the last O(n) pass of a SUPG query: once tau is
estimated from the tiny labeled sample, every shard must be scanned for
{x : A(x) >= tau}. Materializing a boolean mask per query costs one full
host-side allocation per corpus; at 1e9 records that is the memory wall the
streaming plane removes. The scores are viewed as (rows, 128) — records on
the 128 lanes — and the kernel fuses, per row of 128 records:

    compare:  sel[j]  = (A(x_j) >= tau) & (A(x_j) >= 0)   (-1 marks unscored
              records / padding — they are never emitted, regardless of tau)
    rank:     rank[j] = sel[0] + ... + sel[j]  (an MXU product of the row
              with the 128 x 128 upper-triangular ones matrix)
    compact:  out[k]  = j of the k-th selected record of the row, -1 for
              k >= the row's count

The compaction is one more MXU product: the one-hot slot mask
[rank[j] - 1 == k] & sel[j] contracted against the lane ids j + 1. Every
operand is a small integer (0/1 masks, lane ids <= 128), which bfloat16
holds exactly, so the products are exact at the MXU's default precision.
The host stitches rows back together (`ops.threshold_select`): row r's
entries >= 0, plus r * 128, are its selected indices in ascending order.

Layout: grid (rows // block_rows,); each step streams one
(block_n // 128, 128) tile — a whole number of (8, 128) float32 tiles —
and writes the same-shaped int32 tile of compacted lane ids. tau rides in
SMEM. Compiled on TPU, `interpret=True` emulation elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
BLOCK_QUANTUM = LANES * SUBLANES      # block_n must be a multiple of this


def _select_kernel(tau_ref, s_ref, idx_ref):
    tau = tau_ref[0]
    upper = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1), 1.0, 0.0)
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane_ids = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
                + 1).astype(jnp.float32)

    for g in range(s_ref.shape[0] // SUBLANES):
        rows = slice(g * SUBLANES, (g + 1) * SUBLANES)
        s = s_ref[rows, :].astype(jnp.float32)          # (8, 128)
        sel = jnp.logical_and(s >= 0.0, s >= tau)       # padding = -1
        rank = jax.lax.dot_general(
            jnp.where(sel, 1.0, 0.0), upper, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        slot = jnp.where(sel, rank.astype(jnp.int32) - 1, -1)
        out = jnp.zeros((SUBLANES, LANES), jnp.float32)
        for r in range(SUBLANES):
            onehot = jnp.where(slot[r:r + 1] == slot_iota, 1.0, 0.0)
            # Every row of `lane_ids` is the same, so every row of the
            # product is row r's compacted lane ids (+1, 0 for no record).
            compact = jax.lax.dot_general(
                lane_ids, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # (8, 128 slots)
            out = jnp.where(row_iota == r, compact, out)
        idx_ref[rows, :] = out.astype(jnp.int32) - 1


def _check_layout(block_n: int) -> None:
    """Raise ValueError unless the kernel's tiling covers this block."""
    if block_n <= 0 or block_n % BLOCK_QUANTUM:
        raise ValueError(
            f"threshold_select kernel needs block_n to be a positive "
            f"multiple of {BLOCK_QUANTUM}, got {block_n}")


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def threshold_select_rows(scores, tau, block_n=4096, interpret=False):
    """scores: (N,) float; entries < 0 (unscored sentinel/padding) are never
    selected. Returns (ceil(N / block_n) * block_n // 128, 128) int32: row
    r holds the ascending lane ids of the selected records among records
    r * 128 ... r * 128 + 127, followed by -1s.
    """
    _check_layout(block_n)
    n = scores.shape[0]
    pad = (-n) % block_n
    if pad:
        scores = jnp.concatenate(
            [scores, jnp.full((pad,), -1.0, scores.dtype)])
    rows = scores.shape[0] // LANES
    block_rows = block_n // LANES
    tau_arr = jnp.full((1,), tau, jnp.float32)

    return pl.pallas_call(
        _select_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tau_arr, scores.reshape(rows, LANES))
