"""The grouped matmul the MoE layer calls.

The platform picks the path: on TPU the Pallas kernel (`moe_gmm.gmm`,
device ops named `moe_gmm`), elsewhere `jax.lax.ragged_dot`. Both
accumulate in float32 and return lhs's dtype. The backward pass is
`ragged_dot`'s, so a loss through the MoE layer stays differentiable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.moe_gmm import TILE_M, gmm


def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


@jax.custom_vjp
def moe_gmm(lhs, rhs, group_sizes):
    """lhs (m, k) rows grouped in order, rhs (groups, k, n), group_sizes
    (groups,) int32 with sum <= m -> (m, n). Rows past the last group are
    unspecified: callers read only grouped rows."""
    if jax.default_backend() != "tpu":
        return _ragged(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    pad = -m % TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return gmm(lhs, rhs, group_sizes)[:m]


def _fwd(lhs, rhs, group_sizes):
    return moe_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: _ragged(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), None)


moe_gmm.defvjp(_fwd, _bwd)
