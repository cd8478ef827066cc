"""The combine in plain jnp, slot-major: each slot's rows gathered as a
(k, n, d) array (the reshape is free in this order) and summed with the
gates in float32. The MoE layer's combine off TPU, and its backward."""
from __future__ import annotations

import jax.numpy as jnp


def combine_ref(y, pos, gate_vals):
    """y (rows, d), pos (n, k) int32 (out of range where no held expert
    took the slot), gate_vals (n, k) -> (n, d) float32."""
    n, k = pos.shape
    ya = jnp.take(y, pos.T.reshape(k * n), axis=0, mode="fill",
                  fill_value=0).reshape(k, n, y.shape[1])
    return jnp.einsum("knd,kn->nd", ya.astype(jnp.float32),
                      gate_vals.T.astype(jnp.float32))
