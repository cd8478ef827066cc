"""The MoE layer's combine over the held experts, in token tiles, reading
only the rows that the held experts computed.

    out[t] = sum over the slots j of token t that reached a held expert:
             gate[t, j] * y[pos[t, j]]

`y` (rows, d) holds the held experts' output rows one group after another
(expert e's group is rows start[e] .. start[e] + sizes[e] - 1). The
dispatch's counting sort puts each group's rows in ascending token order,
and a token sends at most one row to an expert. So the rows that the
tokens of a tile [t0, t0 + T) send to expert e are one contiguous range of
its group, of at most T rows: its offset is start[e] plus e's assignments
from tokens before t0.

The grid is one axis of visits: for each token tile, for each held expert
whose range in the tile is not empty, each aligned R-row window of y that
the range touches (a tile with no held rows at all is visited once, to
write its zeros). Which tile, which window and which rows of the window
are in the range ride in SMEM as scalar-prefetched metadata
(`visit_metadata`), whose count of visits sizes the grid at run time, as
in `moe_gmm`. A visit zeroes the window's rows outside the range (rows
past the last group are unwritten and may hold NaN), selects each row into
its token's place with a 0/1 (T, R) matrix on the MXU (one nonzero a
column, so every product is exact), scales each token by its gate and adds
into the tile's float32 output, which stays in VMEM while its visits run
and is written once. No (rows, d) gather is ever written.

Compiled on TPU, `interpret=True` elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_R = 128                  # rows of y a visit reads
TILE_T_MAX = 512              # tokens a tile
OUT_TILE_BYTES = 4 * 2 ** 20  # one tile's float32 output, (T, d)


def tile_t(n: int, d: int) -> int:
    """Tokens a tile: the largest power of two that divides `n`, is at
    most TILE_T_MAX and keeps the (T, d) float32 output tile within
    OUT_TILE_BYTES; `n` itself where no multiple of 8 divides it."""
    t = 8
    while (n % (2 * t) == 0 and 2 * t <= TILE_T_MAX
           and 2 * t * d * 4 <= OUT_TILE_BYTES):
        t *= 2
    return t if n % t == 0 else n


def visit_metadata(pos, sizes, rows: int, tt: int, tr: int):
    """(tile, window, first and end row within the window) of each grid
    step, and the steps to run. Steps past the count are padding the grid
    never runs."""
    n, k = pos.shape
    held = sizes.shape[0]
    tiles = n // tt
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    # The held expert of each (token, slot); `held` where none is held.
    expert = jnp.sum(pos[..., None] >= ends, axis=-1, dtype=jnp.int32)
    cnt = jnp.sum(expert.reshape(tiles, tt * k, 1) == jnp.arange(held),
                  axis=1, dtype=jnp.int32)                  # (tiles, held)
    off = ends - sizes + jnp.cumsum(cnt, axis=0) - cnt
    first = off // tr
    windows = jnp.where(cnt > 0, (off + cnt - 1) // tr - first + 1, 0)
    # A tile with no held rows is visited once (an empty range): its
    # output block is written, as zeros.
    windows = windows.at[:, 0].max(
        (jnp.sum(windows, axis=1) == 0).astype(jnp.int32))
    off, cnt, first, windows = (a.reshape(-1)
                                for a in (off, cnt, first, windows))
    steps = rows // tr + 2 * tiles * held    # every range may touch 2 more
    pair = jnp.repeat(jnp.arange(tiles * held, dtype=jnp.int32), windows,
                      total_repeat_length=steps)
    step_start = jnp.cumsum(windows) - windows
    win = jnp.clip(first[pair] + jnp.arange(steps, dtype=jnp.int32)
                   - step_start[pair], 0, rows // tr - 1)
    lo = off[pair] - win * tr
    return pair // held, win, lo, lo + cnt[pair], jnp.sum(windows)


def _kernel(tile, win, lo, hi, tok, gate, y, out, *, tt, tr):
    s = pl.program_id(0)
    i = tile[s]

    @pl.when((s == 0) | (tile[jnp.maximum(s - 1, 0)] != i))
    def _():
        out[...] = jnp.zeros_like(out)

    @pl.when(lo[s] < hi[s])
    def _():
        r_col = jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
        r_row = jax.lax.broadcasted_iota(jnp.int32, (1, tr), 1)
        ys = jnp.where((r_col >= lo[s]) & (r_col < hi[s]), y[...],
                       jnp.zeros((), y.dtype))
        sel = ((jax.lax.broadcasted_iota(jnp.int32, (tt, tr), 0)
                == tok[...] - i * tt)
               & (r_row >= lo[s]) & (r_row < hi[s]))
        g = jnp.sum(jnp.where(sel, gate[...], 0.0), axis=1, keepdims=True)
        out[...] += g * jnp.dot(sel.astype(ys.dtype), ys,
                                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tt", "tr", "interpret"))
def combine(y, pos, gate_vals, row_token, sizes, *, tt, tr=TILE_R,
            interpret=False):
    """y (rows, d) grouped rows, pos (n, k) int32 row of each (token,
    slot) (>= sum(sizes) where no held expert took it), gate_vals (n, k)
    float32, row_token (rows,) int32 token of each grouped row, sizes
    (held,) int32 -> (n, d) float32. rows must be a multiple of `tr` and
    n of `tt`."""
    rows, d = y.shape
    n, k = pos.shape
    if rows % tr or n % tt:
        raise ValueError(f"moe_combine needs rows a multiple of {tr} and "
                         f"tokens of {tt}, got {rows} and {n}")
    row_gate = jnp.zeros((rows,), jnp.float32).at[pos.reshape(-1)].set(
        gate_vals.reshape(-1).astype(jnp.float32), mode="drop")
    tile, win, lo, hi, steps = visit_metadata(
        pos, sizes.astype(jnp.int32), rows, tt, tr)
    lane_rows = pl.BlockSpec((None, 1, tr),
                             lambda s, t, w, a, b: (w[s], 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tt=tt, tr=tr),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                lane_rows,
                lane_rows,
                pl.BlockSpec((tr, d), lambda s, t, w, a, b: (w[s], 0)),
            ],
            out_specs=pl.BlockSpec((tt, d),
                                   lambda s, t, w, a, b: (t[s], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="moe_combine",
    )(tile, win, lo, hi, row_token.reshape(rows // tr, 1, tr),
      row_gate.reshape(rows // tr, 1, tr), y)
