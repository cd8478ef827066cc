"""Public wrapper for the fused threshold-selection kernel.

`backend="auto"` compiles the Pallas kernel on TPU and routes to the
pure-numpy nonzero reference elsewhere — the reference IS the CPU
production path (interpret-mode emulation of the one-hot compaction is for
kernel validation, not throughput, so unlike score_hist it is opt-in via
`backend="interpret"`). `backend="ref"` forces the numpy path. A kernel
backend with a `block_n` its tiling cannot take raises instead of
rerouting, so a caller always knows which path ran. All backends return
identical ascending int64 indices, so the streaming plane is
backend-agnostic bit-for-bit.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.threshold_select import ref
from repro.kernels.threshold_select.threshold_select import (
    LANES, threshold_select_rows)


def default_backend() -> str:
    """The engine's platform default: compiled kernel on TPU, numpy
    reference elsewhere (interpret emulation is for kernel validation, not
    CPU throughput)."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def threshold_select(scores, tau, *, block_n: int = 4096,
                     backend: str = "auto") -> np.ndarray:
    """Ascending local indices of {i : scores[i] >= tau, scores[i] >= 0}.

    scores may be any host float array (np.memmap chunks included); entries
    below 0 are the "unscored" sentinel and are never selected. The kernel
    path stitches per-row compacted lane ids on the host — peak memory is
    O(len(scores)), so callers bound memory by chunking the corpus, never
    by masking it whole. Raises ValueError for a kernel backend whose
    tiling does not cover `block_n`.
    """
    n = int(np.asarray(scores).shape[0])
    if n == 0:
        return np.empty(0, np.int64)
    if backend == "auto":
        backend = default_backend()
    if backend == "ref":
        return ref.threshold_select_ref(scores, tau)
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"unknown threshold_select backend {backend!r}")

    rows = np.asarray(threshold_select_rows(
        np.asarray(scores, np.float32), float(tau), block_n=block_n,
        interpret=(backend == "interpret"))).ravel()
    # Flat position p = r * 128 + k holds row r's k-th selected lane id;
    # row-major order keeps the stitched indices ascending.
    pos = np.flatnonzero(rows >= 0)
    return (pos - pos % LANES) + rows[pos].astype(np.int64)
