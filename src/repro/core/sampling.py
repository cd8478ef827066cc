"""Oracle-sample selection: uniform and optimal importance sampling.

Implements the sampling half of the SUPG algorithms:

* uniform i.i.d. sampling (the NoScope / probabilistic-predicates baseline),
* importance sampling with the paper's *optimal* weights  w(x) ∝ sqrt(A(x))·u(x)
  (Theorem 1), with the suboptimal proportional weights w ∝ A(x) kept as a
  baseline for the Figure-8 comparison,
* defensive mixing  w ← 0.9·w/||w||₁ + 0.1·𝟙/|D|  (Owen & Zhou),
* the reweighting factors m(x) = u(x)/w(x) used by Eqs. (11)-(12).

All samplers draw WITH replacement (as the paper's estimators assume i.i.d.
draws from w) via Gumbel-max / categorical sampling, so they run on-device and
shard cleanly over a data axis.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFENSIVE_KAPPA = 0.1  # mass of the uniform mixture component (paper: 0.1)


class WeightedSample(NamedTuple):
    """Result of a sampling round.

    indices:  (s,) int32 record indices into the dataset (with replacement)
    m:        (s,) float32 reweighting factors m(x) = u(x)/w(x)
    w:        (s,) float32 the sampling probabilities of the drawn records
    """

    indices: jnp.ndarray
    m: jnp.ndarray
    w: jnp.ndarray


def uniform_probs(n):
    return jnp.full((n,), 1.0 / n, jnp.float32)


def sqrt_proxy_weights(scores, defensive=True, kappa=DEFENSIVE_KAPPA):
    """Theorem-1 optimal weights: w ∝ sqrt(A(x)) with defensive mixing."""
    w = jnp.sqrt(jnp.clip(jnp.asarray(scores, jnp.float32), 0.0, 1.0))
    return _normalize_and_mix(w, defensive, kappa)


def proportional_proxy_weights(scores, defensive=True, kappa=DEFENSIVE_KAPPA):
    """Baseline weights w ∝ A(x) — provably no better than uniform (Sec 10.2)."""
    w = jnp.clip(jnp.asarray(scores, jnp.float32), 0.0, 1.0)
    return _normalize_and_mix(w, defensive, kappa)


def _normalize_and_mix(w, defensive, kappa):
    n = w.shape[0]
    tot = jnp.sum(w)
    # Degenerate all-zero proxy: fall back to uniform.
    w = jnp.where(tot > 0, w / jnp.maximum(tot, 1e-30), 1.0 / n)
    if defensive:
        w = (1.0 - kappa) * w + kappa / n
    return w


def sample_uniform(key, n, s):
    """Uniform with-replacement sample of s records out of n."""
    idx = jax.random.randint(key, (s,), 0, n)
    m = jnp.ones((s,), jnp.float32)  # u/w = 1 for uniform
    return WeightedSample(idx, m, jnp.full((s,), 1.0 / n, jnp.float32))


def _inverse_cdf_draw(key, probs, s):
    """s with-replacement categorical draws in O(n + s log n) memory.

    jax.random.categorical materializes an (s, n) Gumbel field — fatal at
    n ~ 1e6+. Inverse-CDF transform sampling (cumsum + searchsorted) is the
    standard streaming-scale substitute and is exactly equivalent in
    distribution (up to fp32 cdf rounding; the cdf is renormalized by its
    final value so total mass is exactly 1).
    """
    cdf = jnp.cumsum(probs)
    cdf = cdf / cdf[-1]
    u = jax.random.uniform(key, (s,), jnp.float32)
    idx = jnp.searchsorted(cdf, u, side="left")
    return jnp.clip(idx, 0, probs.shape[0] - 1).astype(jnp.int32)


def sample_weighted(key, probs, s):
    """With-replacement sample from an explicit probability vector."""
    probs = jnp.asarray(probs, jnp.float32)
    n = probs.shape[0]
    idx = _inverse_cdf_draw(key, probs, s)
    w_drawn = probs[idx]
    m = (1.0 / n) / jnp.maximum(w_drawn, 1e-38)
    return WeightedSample(idx, m, w_drawn)


def sample_weighted_masked(key, probs, mask, s):
    """Weighted sampling restricted to records where mask=1 (stage 2 of PT).

    Probabilities are renormalized over the masked subset; m(x) is computed
    w.r.t. the *uniform distribution on the masked subset*, matching the
    paper's stage-2 estimator which treats D' as the population.
    """
    probs = jnp.asarray(probs, jnp.float32) * jnp.asarray(mask, jnp.float32)
    tot = jnp.sum(probs)
    n_sub = jnp.maximum(jnp.sum(mask), 1.0)
    probs = jnp.where(tot > 0, probs / jnp.maximum(tot, 1e-30),
                      jnp.asarray(mask, jnp.float32) / n_sub)
    idx = _inverse_cdf_draw(key, probs, s)
    w_drawn = probs[idx]
    m = (1.0 / n_sub) / jnp.maximum(w_drawn, 1e-38)
    return WeightedSample(idx, m, w_drawn)


# ---------------------------------------------------------------------------
# Host-side CDF primitives for the engine's cached sampling state
# ---------------------------------------------------------------------------
# The SelectionEngine's cached state is *hierarchical*: per shard it
# persists only raw masses accumulated during the sketch pass — per chunk
# and per BLOCK_RECORDS-record block, O(n / BLOCK_RECORDS) floats — and
# resolves record-level draws at query time in three steps: a categorical
# over chunk masses, a search over the allocated chunk's block-mass prefix,
# then an exact inverse-CDF draw over freshly computed p(x) of just the
# blocks hit (`draw_in_blocks`). Because a chunk's (block's) defensive-
# mixture mass is exactly the sum of its records' p(x), the telescoped
# product reproduces the global p(x), so m(x) = (1/n)/p(x) stays exact with
# no O(n) state. float64 keeps the prefix sums faithful at 1e8+ records.

BLOCK_RECORDS = 1024   # records a block: the within-chunk unit of a draw


def normalized_cdf(weights) -> np.ndarray:
    """Inclusive float64 prefix CDF, renormalized to end exactly at 1
    (over a whole chunk's p(x): the reference `draw_in_blocks` is tested
    against)."""
    w = np.asarray(weights, np.float64)
    cdf = np.cumsum(w)
    total = cdf[-1] if cdf.size else 0.0
    if not total > 0:
        raise ValueError("normalized_cdf needs positive total mass")
    return cdf / total


def draw_from_cdf(cdf: np.ndarray, u) -> np.ndarray:
    """Vectorized inverse-CDF draws: indices such that cdf[i-1] <= u < cdf[i]."""
    idx = np.searchsorted(cdf, np.asarray(u, np.float64), side="left")
    return np.minimum(idx, cdf.shape[0] - 1).astype(np.int64)


class ChunkMasses(NamedTuple):
    """Per-chunk and per-block raw sampling masses for one shard (the
    persistent half of the hierarchical sampler — O(n / BLOCK_RECORDS),
    never O(n_records)).

    Accumulated during the chunked sketch pass at engine construction: the
    chunk is already in cache there, so the extra float64 reductions are
    effectively free. Blocks are BLOCK_RECORDS consecutive records of one
    chunk (a chunk's last block may be shorter, none straddles chunks), laid
    out chunk after chunk. The sums are raw — independent of Z, kappa and
    n — so an append only adds its own shards' entries. `sizes` counts
    *all* records in the chunk (unscored sentinels included) because the
    defensive uniform component kappa/n gives every record mass, exactly
    like the dense p(x) formula.
    """

    sum_sqrt: np.ndarray   # (n_chunks,) float64 Σ sqrt(clip(A)) per chunk
    sum_a: np.ndarray      # (n_chunks,) float64 Σ clip(A) per chunk
    sizes: np.ndarray      # (n_chunks,) int64 record count per chunk
    block_sqrt: np.ndarray  # (n_blocks,) float64 Σ sqrt(clip(A)) per block
    block_a: np.ndarray     # (n_blocks,) float64 Σ clip(A) per block

    def raw(self, scheme: str) -> np.ndarray:
        return self.sum_sqrt if scheme == "sqrt" else self.sum_a

    def block_raw(self, scheme: str, chunk_id: int) -> np.ndarray:
        """One chunk's per-block raw masses for `scheme`."""
        nb = -(-self.sizes // BLOCK_RECORDS)
        start = int(nb[:chunk_id].sum())
        blocks = self.block_sqrt if scheme == "sqrt" else self.block_a
        return blocks[start:start + int(nb[chunk_id])]

    @classmethod
    def empty(cls) -> "ChunkMasses":
        return cls(np.empty(0, np.float64), np.empty(0, np.float64),
                   np.empty(0, np.int64), np.empty(0, np.float64),
                   np.empty(0, np.float64))


def chunk_raw_masses(scores_chunk
                     ) -> Tuple[float, float, np.ndarray, np.ndarray]:
    """Float64 Σ sqrt(A) and Σ A over one chunk, then the same two sums
    per BLOCK_RECORDS-record block (sentinels contribute 0)."""
    a = np.clip(np.asarray(scores_chunk, np.float32), 0.0, 1.0)
    r = np.sqrt(a)
    starts = np.arange(0, a.shape[0], BLOCK_RECORDS)
    return (float(np.sum(r, dtype=np.float64)),
            float(np.sum(a, dtype=np.float64)),
            np.add.reduceat(r, starts, dtype=np.float64),
            np.add.reduceat(a, starts, dtype=np.float64))


def defensive_chunk_mass(raw: np.ndarray, sizes: np.ndarray, z: float,
                         kappa: float, n_total: int) -> np.ndarray:
    """Total defensive-mixture draw probability of each chunk.

    Summing p(x) = (1-kappa)·raw(x)/Z + kappa/n over a chunk gives
    (1-kappa)·Σraw/Z + kappa·|chunk|/n — computable from the cached chunk
    masses alone, so the chunk-level categorical needs no record access.
    """
    z = max(float(z), 1e-30)
    return ((1.0 - kappa) * np.asarray(raw, np.float64) / z
            + kappa * np.asarray(sizes, np.float64) / n_total)


def append_cdf(cum: np.ndarray, new_masses) -> np.ndarray:
    """Extend an *unnormalized* float64 chunk-mass prefix sum in place of a
    full rebuild — the live plane's CDF-append path.

    `np.cumsum` is a sequential left fold (``c[i] = c[i-1] + m[i]``), so
    continuing the fold from the existing tail reproduces, bit for bit, the
    prefix sum a cold pass over the concatenated mass vector would compute.
    That identity is what lets incremental ingestion extend per-shard
    chunk-mass CDFs without re-reading any old chunk while staying
    bitwise-equal to a cold engine rebuild (`tests/test_live.py` property-
    tests the split-vs-full equality).

    >>> full = np.cumsum(np.asarray([0.3, 0.2, 0.5, 0.1], np.float64))
    >>> grown = append_cdf(np.cumsum(np.asarray([0.3, 0.2], np.float64)),
    ...                    [0.5, 0.1])
    >>> bool(np.array_equal(full, grown))
    True
    """
    new = np.asarray(new_masses, np.float64)
    cum = np.asarray(cum, np.float64)
    if cum.size == 0:
        return np.cumsum(new)
    if new.size == 0:
        return cum.copy()
    # Seed the cumsum with the existing tail so the fold *continues* —
    # ``cum[-1] + np.cumsum(new)`` would regroup the additions and drift.
    return np.concatenate(
        [cum, np.cumsum(np.concatenate([cum[-1:], new]))[1:]])


def chunk_mass_cdf(raw: np.ndarray, sizes: np.ndarray, z: float,
                   kappa: float, n_total: int) -> Tuple[float, np.ndarray]:
    """One shard's (total mass, normalized chunk-mass CDF) for the
    hierarchical draw — the single construction path shared by cold engine
    builds and the ingest plane's epoch extensions, so both produce
    bit-identical sampling state from identical chunk masses."""
    m_c = defensive_chunk_mass(raw, sizes, z, kappa, n_total)
    total = float(m_c.sum())
    if not total > 0:
        raise ValueError(
            "shard has no sampling mass (kappa=0 with an all-zero proxy?)")
    return total, append_cdf(np.empty(0, np.float64), m_c) / total


def defensive_probs(scores_chunk, scheme: str, z: float, kappa: float,
                    n_total: int) -> np.ndarray:
    """Global draw probabilities p(x) for the records of one chunk.

    Bit-identical to the formula the dense per-record path used (float32
    p values), so the hierarchical draw's m(x) factors match the dense
    sampler's exactly at matched records.
    """
    z = max(float(z), 1e-30)
    a = np.clip(np.asarray(scores_chunk, np.float32), 0.0, 1.0)
    raw = np.sqrt(a) if scheme == "sqrt" else a
    return ((1.0 - kappa) * raw / z + kappa / n_total).astype(np.float32)


class BlockDraw(NamedTuple):
    """Draws resolved inside one chunk by `draw_in_blocks`."""

    local: np.ndarray   # (d,) int64 record index within the chunk
    p: np.ndarray       # (d,) float32 p(x) of the drawn records
    blocks: int         # distinct blocks the draws fell in
    records: int        # records whose p(x) was computed


def draw_in_blocks(chunk, block_raw: np.ndarray, u, scheme: str, z: float,
                   kappa: float, n_total: int) -> BlockDraw:
    """Inverse-CDF draws within one chunk, reading only the blocks hit.

    The chunk's defensive block-mass prefix M_b = (1-kappa)·R_b/Z +
    kappa·C_b/n (R_b the raw block-mass prefix, C_b the record-count
    prefix) places each target T = u·M_last in a block; p(x) is computed
    over each distinct block hit, once, and T − M_{b-1} is searched in
    that block's float64 prefix sum. The same u picks the record a whole-
    chunk CDF would but at float rounding edges, and p is `defensive_probs`
    itself, so m(x) = (1/n)/p(x) matches it bit for bit.
    """
    size = chunk.shape[0]
    n_blocks = block_raw.shape[0]
    z = max(float(z), 1e-30)
    counts = np.minimum(np.arange(1, n_blocks + 1) * BLOCK_RECORDS, size)
    prefix = ((1.0 - kappa) * np.cumsum(block_raw) / z
              + kappa * counts / n_total)
    target = np.asarray(u, np.float64) * prefix[-1]
    blk = np.minimum(np.searchsorted(prefix, target, side="left"),
                     n_blocks - 1)
    hit, row = np.unique(blk, return_inverse=True)
    # One gather of every hit block; a short last block is padded with
    # its final record at zero mass.
    idx = hit[:, None] * BLOCK_RECORDS + np.arange(BLOCK_RECORDS)
    p = defensive_probs(np.asarray(chunk)[np.minimum(idx, size - 1)],
                        scheme, z, kappa, n_total)
    p[idx >= size] = 0.0
    cum = np.cumsum(p, axis=1, dtype=np.float64)
    offset = target - np.where(blk > 0, prefix[blk - 1], 0.0)
    # Complex numbers order lexicographically (real, then imaginary), so
    # one searchsorted finds every draw's record in its own block's row.
    keys = (np.arange(hit.size)[:, None] + 1j * cum).ravel()
    found = np.searchsorted(keys, row + 1j * offset, side="left")
    lengths = np.minimum(size - hit * BLOCK_RECORDS, BLOCK_RECORDS)
    local = np.minimum(found - row * BLOCK_RECORDS, lengths[row] - 1)
    return BlockDraw(hit[row] * BLOCK_RECORDS + local, p[row, local],
                     int(hit.size), int(lengths.sum()))


@functools.partial(jax.jit, static_argnames=("s", "scheme", "defensive"))
def draw_oracle_sample(key, scores, s, scheme="sqrt", defensive=True):
    """One-stop sampler used by the query layer.

    scheme: 'uniform' | 'sqrt' (Theorem 1 optimal) | 'prop' (baseline).
    """
    n = scores.shape[0]
    if scheme == "uniform":
        return sample_uniform(key, n, s)
    if scheme == "sqrt":
        probs = sqrt_proxy_weights(scores, defensive=defensive)
    elif scheme == "prop":
        probs = proportional_proxy_weights(scores, defensive=defensive)
    else:
        raise ValueError(f"unknown sampling scheme: {scheme}")
    return sample_weighted(key, probs, s)
