"""Plain reference for the grouped matmul: one matmul per group."""
from __future__ import annotations

import numpy as np


def gmm_ref(lhs, rhs, group_sizes):
    """lhs (m, k), rhs (groups, k, n), group_sizes (groups,) -> (m, n)
    float64; rows past the last group are zero."""
    lhs = np.asarray(lhs, np.float64)
    rhs = np.asarray(rhs, np.float64)
    out = np.zeros((lhs.shape[0], rhs.shape[2]))
    start = 0
    for g, size in enumerate(np.asarray(group_sizes)):
        out[start:start + size] = lhs[start:start + size] @ rhs[g]
        start += int(size)
    return out
