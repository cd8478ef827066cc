"""Grouped matrix multiplication over ragged row groups — the held
experts' SwiGLU projections of the dropless MoE layer.

    out[r] = lhs[r] @ rhs[g]   for every row r of group g

`lhs` (m, k) holds the groups' rows one after another (group g is rows
offsets[g] .. offsets[g + 1] - 1, offsets the running sum of
`group_sizes`), `rhs` (groups, k, n) one matrix a group. The grid is
(n tiles, m tiles visited, k tiles). The m-tile axis walks only the tiles
that hold rows of some group: a tile that two groups share is visited
once for each, consecutively, and each visit stores only its own group's
rows (a row mask), so no group is padded to a tile and rows past the last
group are never computed (their output is left unwritten). Which group
and which m tile a grid step works on rides in SMEM as scalar-prefetched
metadata (`group_metadata`), whose count of visited tiles sizes the grid
at run time — the layout of jax's megablox `gmm`, cut to what the MoE
layer needs: no group offset, no transposed rhs, k and n whole tiles.

Products accumulate in float32 in VMEM across the k tiles and are stored
in the output dtype. Compiled on TPU, `interpret=True` elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_TILE = 1536          # the widest k or n tile: VMEM holds two of each
TILE_M = 512


def tile(dim: int) -> int:
    """The widest multiple of 128 that divides `dim` and is at most
    MAX_TILE; `dim` itself where it is no multiple of 128 (one block)."""
    if dim % LANES:
        return dim
    return max(t for t in range(LANES, min(dim, MAX_TILE) + 1, LANES)
               if dim % t == 0)


def group_metadata(group_sizes, m: int, tm: int):
    """(offsets (g+1,), group of each grid step, m tile of each grid step,
    steps to run). Steps past the count are padding the grid never runs."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    steps = m // tm + g - 1            # every group may share one tile
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=steps)
    step_start = jnp.cumsum(tiles) - tiles
    m_tiles = (first[group_ids] + jnp.arange(steps, dtype=jnp.int32)
               - step_start[group_ids])
    m_tiles = jnp.clip(m_tiles, 0, m // tm - 1)
    return offsets, group_ids, m_tiles, jnp.sum(tiles)


def _kernel(offsets, group_ids, m_tiles, lhs, rhs, out, acc, *, tm, tiles_k):
    step, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k == tiles_k - 1)
    def _():
        g = group_ids[step]
        rows = m_tiles[step] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        out[...] = jnp.where(mine, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def gmm(lhs, rhs, group_sizes, *, tm=TILE_M, interpret=False):
    """lhs (m, k), rhs (groups, k, n), group_sizes (groups,) int32 with
    sum <= m -> (m, n) in lhs.dtype. m must be a multiple of `tm`; rows
    past the last group are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if m % tm:
        raise ValueError(f"moe_gmm needs rows a multiple of {tm}, got {m}")
    tk, tn = tile(k), tile(n)
    offsets, group_ids, m_tiles, steps = group_metadata(
        group_sizes.astype(jnp.int32), m, tm)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=k // tk),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, steps, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, s, kk, o, g, t: (t[s], kk)),
                pl.BlockSpec((None, tk, tn),
                             lambda j, s, kk, o, g, t: (g[s], kk, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, s, kk, o, g, t: (t[s], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_ids, m_tiles, lhs, rhs)
