"""The combine the MoE layer calls.

The platform picks the path: on TPU the Pallas kernel (`moe_combine.combine`,
device ops named `moe_combine`), which reads only the held experts' rows,
with its tiles from the shapes; elsewhere the slot-major jnp combine
(`ref.combine_ref`). Both return float32. The backward pass is the jnp
combine's, so a loss through the MoE layer stays differentiable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_combine.moe_combine import TILE_R, combine, tile_t
from repro.kernels.moe_combine.ref import combine_ref


@jax.custom_vjp
def moe_combine(y, pos, gate_vals, row_token, sizes):
    """y (rows, d) the held experts' rows grouped in order, pos (n, k)
    int32 row of each (token, slot) (>= sum(sizes) where no held expert
    took it), gate_vals (n, k), row_token (rows,) int32 token of each
    grouped row, sizes (held,) int32 -> (n, d) float32. Rows of y past
    sum(sizes) are never read into the sum."""
    if jax.default_backend() != "tpu":
        return combine_ref(y, pos, gate_vals)
    rows = y.shape[0]
    pad = -rows % TILE_R
    if pad:
        y = jnp.pad(y, ((0, pad), (0, 0)))
        row_token = jnp.pad(row_token, (0, pad))
    return combine(y, pos, gate_vals, row_token, sizes,
                   tt=tile_t(pos.shape[0], y.shape[1]))


def _fwd(y, pos, gate_vals, row_token, sizes):
    return (moe_combine(y, pos, gate_vals, row_token, sizes),
            (y, pos, gate_vals))


def _bwd(res, g):
    y, pos, gate_vals = res
    _, vjp = jax.vjp(lambda a, b: combine_ref(a, pos, b), y, gate_vals)
    dy, dgate = vjp(g)
    return dy, None, dgate, None, None


moe_combine.defvjp(_fwd, _bwd)
