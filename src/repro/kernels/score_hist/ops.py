"""Jit'd public wrapper for the fused score sketch.

`backend="auto"` (the default) runs the Pallas kernel compiled on TPU and
`interpret=True` emulation everywhere else, so callers can treat the fused
kernel as the default sketch path without platform checks. A kernel
backend with a `num_bins` or `block_n` its tiling cannot take raises;
`backend="ref"` is the pure-jnp scatter-add reference.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.score_hist import ref
from repro.kernels.score_hist.score_hist import LANES
from repro.kernels.score_hist.score_hist import score_hist as _kernel


def kernel_supported(num_bins: int) -> bool:
    """Whether the fused kernel's (hi, lo) bin layout covers this count."""
    return num_bins > 0 and num_bins % LANES == 0


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "backend", "block_n"))
def score_hist(scores, num_bins=4096, *, backend="auto", block_n=8192):
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "interpret"
    if backend == "ref":
        return ref.score_hist_ref(scores, num_bins)
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"unknown score_hist backend {backend!r}")
    return _kernel(scores, num_bins=num_bins, block_n=block_n,
                   interpret=(backend == "interpret"))
