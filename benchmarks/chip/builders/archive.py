"""An archive of proxy scores, served: `configs/*.json` of kind `archive`.

Scores A ~ Beta(alpha, 1) and labels O ~ Bernoulli(A) are made from the
seed on the device (`chipbench.deploy.make_corpus`), split into
`shards`, and served by one `SelectionEngine` behind a
`SelectionServer`. The oracle looks the true label up.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import deploy


@dataclasses.dataclass
class Archive:
    """The hosted corpus: its data, its oracle, and the served engine."""
    scores: np.ndarray
    labels: np.ndarray
    shards: List[np.ndarray]
    offsets: np.ndarray
    engine: object
    server: object
    oracle: Callable

    def close(self) -> None:
        self.server.close()


def label_oracle(labels: np.ndarray) -> Callable:
    """The oracle: a lookup of the true labels, with no added latency."""
    def oracle(indices):
        with TraceAnnotation("bench.oracle"):
            return labels[np.asarray(indices, np.int64)].astype(np.float32)
    return oracle


def build(cfg: dict, seed: int) -> Archive:
    """Scores and labels from the seed, then the engine and its server."""
    from repro.core.engine import SelectionEngine
    from repro.serve import SelectionServer

    scores, labels = deploy.make_corpus(cfg, seed)
    shards = np.array_split(scores, int(cfg["shards"]))
    offsets = np.concatenate([[0], np.cumsum([s.size for s in shards])])
    engine = SelectionEngine(shards, num_bins=int(cfg["num_bins"]),
                             chunk_records=int(cfg["chunk_records"]),
                             workers=int(cfg["workers"]))
    oracle = label_oracle(labels)
    server = SelectionServer(engine, oracle,
                             max_inflight=int(cfg["max_inflight"]))
    return Archive(scores, labels, shards, offsets.astype(np.int64),
                   engine, server, oracle)
