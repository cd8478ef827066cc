"""Binned score sketches — the cluster-scale selection data plane.

At production scale the proxy scores A(x) for ~1e9 records live sharded
across data-parallel hosts; a literal port of the paper would centrally sort
them (O(n log n), one host). We adapt: all *global* quantities the SUPG
algorithms need are derivable from a one-pass fixed-width histogram sketch:

  counts[b]    |{x : A(x) in bin b}|      -> |D(tau)| set sizes, rank->tau
  sum_w[b]     sum of sqrt(A(x)) in bin b -> normalization of Theorem-1 weights
  sum_a[b]     sum of A(x) in bin b       -> normalization of 'prop' weights

The sample-side statistics (s <= ~1e4 labeled records) stay exact and are
gathered to every host; only the dataset-side reductions are sketched. The
D'-cutoff snap is *conservative* (rounds the threshold down a bin, enlarging
D'), which preserves validity: stage-2 restriction is an efficiency device,
never a correctness requirement.

The per-shard sketch pass is the HBM-bandwidth hot spot and runs through the
fused Pallas kernel (kernels/score_hist) by default. On TPU the kernel is
compiled and a bin count its tiling cannot take raises; off TPU it runs in
`interpret=True` emulation, and the pure-jnp scatter-add reference takes the
bin counts the kernel does not cover.

`weight_normalizers` feeds the SelectionEngine's cached sampling state: the
global Σ sqrt(A), Σ A and n extracted from one merged sketch are the only
cross-shard quantities the defensive-mixture draw probabilities need, so the
engine never re-reduces raw shards per query. `chunk_sketch_stats` is the
per-chunk unit of the engine's streaming construction pass: it fuses the
sketch reduction with the float64 per-chunk raw masses the hierarchical
(shard → chunk → record) sampler persists, so bounded-memory importance
sampling costs no extra data pass.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.score_hist import ops as hist_ops

DEFAULT_BINS = 4096


class ScoreSketch(NamedTuple):
    counts: jnp.ndarray   # (B,) float32 record counts per bin
    sum_w: jnp.ndarray    # (B,) float32 sum of sqrt(A) per bin
    sum_a: jnp.ndarray    # (B,) float32 sum of A per bin

    @property
    def num_bins(self):
        return self.counts.shape[0]

    @property
    def total(self):
        return jnp.sum(self.counts)


def bin_index(scores, num_bins=DEFAULT_BINS):
    """Bin id in [0, B) for scores in [0, 1]; bin b covers [b/B, (b+1)/B)."""
    s = jnp.clip(jnp.asarray(scores, jnp.float32), 0.0, 1.0)
    return jnp.minimum((s * num_bins).astype(jnp.int32), num_bins - 1)


def build_sketch(scores, num_bins=DEFAULT_BINS, use_kernel=None):
    """One-pass sketch of a score shard.

    use_kernel: True forces the fused Pallas kernel, False forces the jnp
    scatter-add reference. None (default) picks by platform: on TPU the
    compiled kernel, which raises ValueError for a bin count its tiling
    cannot take; elsewhere the interpret-mode kernel where its tiling covers
    the bin count and the jnp reference where it does not.
    """
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      or hist_ops.kernel_supported(num_bins))
    if use_kernel:
        return ScoreSketch(*hist_ops.score_hist(scores, num_bins))
    scores = jnp.asarray(scores, jnp.float32)
    idx = bin_index(scores, num_bins)
    # Mask the -1 "unscored" sentinel exactly like the kernel path does —
    # partially-scored ScoreStore shards must sketch identically across
    # backends (the sentinel used to be clipped into bin 0 here).
    valid = (scores >= 0.0).astype(jnp.float32)
    a = jnp.clip(scores, 0.0, 1.0)
    counts = jnp.zeros(num_bins, jnp.float32).at[idx].add(valid)
    sum_w = jnp.zeros(num_bins, jnp.float32).at[idx].add(
        jnp.sqrt(a) * valid)
    sum_a = jnp.zeros(num_bins, jnp.float32).at[idx].add(a * valid)
    return ScoreSketch(counts, sum_w, sum_a)


def chunk_sketch_stats(scores_chunk, num_bins=DEFAULT_BINS, use_kernel=None
                       ) -> Tuple[ScoreSketch, float, float]:
    """One streaming-pass unit over a chunk: its ScoreSketch plus the raw
    sampling masses (float64 Σ sqrt(A), Σ A) the hierarchical sampler
    persists per chunk.

    The chunk is already in cache for the sketch reduction, so the two
    extra sums are effectively free — this is what lets the engine cache
    O(n / chunk_records) sampling state instead of per-record CDFs.
    """
    from repro.core import sampling

    chunk32 = np.ascontiguousarray(scores_chunk, np.float32)
    sketch = build_sketch(jnp.asarray(chunk32), num_bins,
                          use_kernel=use_kernel)
    s_sqrt, s_a = sampling.chunk_raw_masses(chunk32)
    return sketch, s_sqrt, s_a


def merge_sketches(*sketches):
    return ScoreSketch(
        sum(s.counts for s in sketches),
        sum(s.sum_w for s in sketches),
        sum(s.sum_a for s in sketches))


def rank_to_threshold(sketch: ScoreSketch, rank):
    """Conservative tau with |{A >= tau}| >= rank, from bin counts.

    Scans bins from the top; returns the *lower edge* of the bin where the
    cumulative count first reaches `rank` (rounding tau down => superset).
    """
    b = sketch.num_bins
    desc_counts = sketch.counts[::-1]
    cum = jnp.cumsum(desc_counts)
    reached = cum >= jnp.asarray(rank, jnp.float32)
    j = jnp.where(jnp.any(reached), jnp.argmax(reached), b - 1)
    bin_id = (b - 1) - j          # original bin index
    return bin_id.astype(jnp.float32) / b


def selection_size(sketch: ScoreSketch, tau):
    """Upper bound on |{x : A(x) >= tau}| from bin counts (bin-granular)."""
    b = sketch.num_bins
    lo_bin = jnp.floor(jnp.clip(tau, 0.0, 1.0) * b).astype(jnp.int32)
    mask = jnp.arange(b) >= lo_bin
    return jnp.sum(sketch.counts * mask)


def weight_normalizers(sketch: ScoreSketch):
    """Global Σ sqrt(A), Σ A and n — denominators for Theorem-1 / prop weights.

    With defensive mixing at some kappa, a record x in a shard has sampling
    probability
        p(x) = (1-kappa) * sqrt(A(x)) / Z_sqrt + kappa / n_total
    computable shard-locally once (Z_sqrt, n_total) are known globally; the
    normalizers themselves are kappa-independent.
    """
    return jnp.sum(sketch.sum_w), jnp.sum(sketch.sum_a), jnp.sum(sketch.counts)
