"""Plain references and the comparisons that decide `correct`.

Each check returns `Check`s: a short name, the number compared, and its
limit. A run is correct when every number is at or under its limit.
The references use only the inputs regenerated from the seed (scores,
labels, tokens, weights) and numpy or plain `jax.numpy`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)   # NaN compares False

    def as_json(self) -> dict:
        """JSON has no infinity: a value that is not finite reads 1e300."""
        v = self.value if math.isfinite(self.value) else 1e300
        return {"value": v, "limit": self.limit}


# -- query answers ---------------------------------------------------------

def reference_set(scores: np.ndarray, tau: float,
                  positives: np.ndarray) -> np.ndarray:
    """{A >= tau} plus the query's labeled positives. The comparison runs
    in the precision of `scores`: float32 scores give the reference,
    scores rounded to a narrower type give the control."""
    above = np.flatnonzero(scores >= np.asarray(tau, np.float32).astype(
        scores.dtype))
    return np.union1d(above, np.asarray(positives, np.int64))


def set_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Records in one set and not the other."""
    return int(np.setxor1d(got, want, assume_unique=False).size)


def binomial_limit(n: int, p: float, tail: float = 1e-6) -> int:
    """The fewest misses k with P(Binomial(n, p) > k) <= tail: more than
    k misses in n queries says the per-query guarantee does not hold."""
    pmf = [math.comb(n, i) * p ** i * (1 - p) ** (n - i)
           for i in range(n + 1)]
    above = 1.0
    for k in range(n + 1):
        above -= pmf[k]
        if above <= tail:
            return k
    return n


class RecallOracle:
    """Recall of an answer given by (tau, labeled positives) against the
    true labels, without materializing the answer."""

    def __init__(self, scores: np.ndarray, truth: np.ndarray):
        self.scores = scores
        self.true_scores = np.sort(scores[truth])
        self.n_true = int(self.true_scores.size)

    def recall(self, tau: float, positives: np.ndarray) -> float:
        above = self.n_true - int(np.searchsorted(
            self.true_scores, np.float32(tau), side="left"))
        pos = np.asarray(positives, np.int64)
        below = int(np.count_nonzero(self.scores[pos] < np.float32(tau)))
        return (above + below) / max(self.n_true, 1)


def recall_misses(records: Sequence, oracle: RecallOracle) -> int:
    """Answers whose recall falls short of their stated target."""
    return sum(oracle.recall(r.tau, r.positives) < r.gamma for r in records)


def check_sample(n: int, k: int, seed: int, stream: str) -> List[int]:
    """k of n items, drawn from the seed."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1),
                                 int.from_bytes(stream.encode()[:8], "big")])
    return sorted(rng.choice(n, size=min(k, n), replace=False).tolist())


# -- ingest ------------------------------------------------------------------

def sketch_count_errors(shard_scores: Sequence[np.ndarray], sketches,
                        num_bins: int) -> int:
    """Records binned differently by each appended shard's sketch than
    by a numpy histogram of its scores."""
    err = 0
    for scores, sk in zip(shard_scores, sketches):
        ids = np.minimum((np.clip(scores, 0.0, 1.0) * np.float32(num_bins))
                         .astype(np.int64), num_bins - 1)[scores >= 0]
        counts = np.bincount(ids, minlength=num_bins)
        err += int(np.abs(np.asarray(sk.counts, np.float64) - counts).sum())
    return err


def logp_gap(served_scores: np.ndarray, ref_logp: np.ndarray) -> float:
    """The widest gap between the served log-score and the reference's
    log-probability of the target token; inf where a score is not a
    positive finite probability."""
    s = np.asarray(served_scores, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(np.log(s) - np.asarray(ref_logp, np.float64))
    gap[~np.isfinite(gap)] = np.inf
    return float(gap.max()) if gap.size else math.inf


def reference_logp(cfg: dict, traffic: dict, seed: int, picks,
                   quantize: bool = False, block: int = 8) -> np.ndarray:
    """The float32 reference's log p(target) for records picked as
    (batch index, row), regenerated from the seed, in blocks; with
    `quantize`, the float8 control."""
    import jax.numpy as jnp

    from chipbench import deploy, llama_ref

    w = deploy.make_weights(cfg, seed, jnp.dtype(cfg["dtype"]))
    out = []
    for i in range(0, len(picks), block):
        part = picks[i:i + block]
        toks = np.stack([np.asarray(deploy.make_token_batch(
            seed, b, int(traffic["batch"]), int(traffic["seq_len"]),
            int(cfg["vocab_size"]), traffic["marker"],
            float(traffic["marker_rate"])))[r] for b, r in part])
        out.append(np.asarray(llama_ref.target_logprob(
            w, toks, cfg, int(cfg["target_token"]), quantize=quantize)))
    return np.concatenate(out) if out else np.empty(0)
