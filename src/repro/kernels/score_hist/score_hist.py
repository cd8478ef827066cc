"""Fused score-sketch histogram — the SUPG selection plane's HBM hot loop.

One pass over a proxy-score shard produces, per histogram bin b:
    counts[b] = |{x : A(x) in bin b}|
    sum_w[b]  = sum of sqrt(A(x))     (Theorem-1 weight normalizer)
    sum_a[b]  = sum of A(x)           ('prop' baseline normalizer)

The pure-jnp path needs one scatter-add pass per statistic; this kernel
fuses all three into a single streaming read — at ~1e9 scores the pass is
HBM-bandwidth-bound (4 GB read, ~5 ms/chip at 819 GB/s), so halving passes
halves selection-plane latency.

Layout: the scores are viewed as (rows, 128) — records on the 128 lanes —
and the grid streams (block_n // 128, 128) tiles into VMEM, so every block
is a whole number of (8, 128) float32 tiles. A bin id b splits into
hi = b // 128 and lo = b % 128. For each row of 128 records the kernel
builds two small one-hot masks with records on the lanes,

    oh_hi[h, j] = [hi(x_j) == h]               (num_bins // 128, 128)
    oh_lo[l, j] = [lo(x_j) == l] * valid(x_j)  (128, 128)

and one MXU contraction over the records,

    acc[h, k*128 + l] += sum_j oh_hi[h, j] * stat_k(x_j) * oh_lo[l, j]

yields the (hi, lo) histogram of all three statistics at once (stat_0 = 1,
stat_1 = sqrt(A), stat_2 = A). That is num_bins // 128 + 128 compares per
record instead of num_bins, and the one-hot masks stay at 64 KiB. The
accumulator, (num_bins // 128, 384) float32 (48 KiB at 4096 bins), lives
in VMEM across the sequential grid. The contraction runs at HIGHEST
precision: at the MXU's default a float32 operand is rounded to bfloat16,
which would cost sum_w and sum_a their low 16 mantissa bits.
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


def _hist_kernel(s_ref, o_ref, *, num_bins):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    n_hi = num_bins // LANES
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (n_hi, LANES), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    n_groups = s_ref.shape[0] // SUBLANES

    def group(g, acc):
        # One (8, 128) tile: elementwise work on whole vregs, then one MXU
        # contraction per row of 128 records.
        r0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        s = s_ref[pl.ds(r0, SUBLANES), :].astype(jnp.float32)
        valid = s >= 0.0                                 # padding marked -1
        a = jnp.clip(s, 0.0, 1.0)
        ids = jnp.minimum((a * num_bins).astype(jnp.int32), num_bins - 1)
        hi = jax.lax.shift_right_logical(ids, LANES.bit_length() - 1)
        lo = jnp.where(valid, jnp.bitwise_and(ids, LANES - 1), -1)
        w = jnp.sqrt(a)
        for r in range(SUBLANES):
            oh_hi = jnp.where(hi[r:r + 1] == hi_iota, 1.0, 0.0)
            oh_lo = jnp.where(lo[r:r + 1] == lo_iota, 1.0, 0.0)
            rhs = jnp.concatenate(
                [oh_lo, oh_lo * w[r:r + 1], oh_lo * a[r:r + 1]], axis=0)
            acc = acc + jax.lax.dot_general(
                oh_hi, rhs, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)    # (n_hi, 3 * 128)
        return acc

    acc = jax.lax.fori_loop(0, n_groups, group,
                            jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc


def _check_layout(num_bins: int, block_n: int) -> None:
    """Raise ValueError unless the kernel's tiling covers these sizes."""
    if num_bins <= 0 or num_bins % LANES:
        raise ValueError(
            f"score_hist kernel needs num_bins to be a positive multiple "
            f"of {LANES}, got {num_bins}")
    if block_n <= 0 or block_n % BLOCK_QUANTUM:
        raise ValueError(
            f"score_hist kernel needs block_n to be a positive multiple "
            f"of {BLOCK_QUANTUM}, got {block_n}")


@functools.partial(jax.jit, static_argnames=("num_bins", "block_n",
                                             "interpret"))
def score_hist(scores, num_bins=4096, block_n=8192, interpret=False):
    """scores: (N,) float in [0,1] (entries < 0 are ignored padding).

    Returns (counts, sum_w, sum_a) each (num_bins,) float32.
    """
    _check_layout(num_bins, block_n)
    n = scores.shape[0]
    pad = (-n) % block_n
    if pad:
        scores = jnp.concatenate(
            [scores, jnp.full((pad,), -1.0, scores.dtype)])
    rows = scores.shape[0] // LANES
    block_rows = block_n // LANES
    n_hi = num_bins // LANES

    kernel = functools.partial(_hist_kernel, num_bins=num_bins)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n_hi, 3 * LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_hi, 3 * LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scores.reshape(rows, LANES))
    # out[h, k * 128 + l] is statistic k of bin h * 128 + l.
    stats = out.reshape(n_hi, 3, LANES).transpose(1, 0, 2)
    stats = stats.reshape(3, num_bins)
    return stats[0], stats[1], stats[2]
