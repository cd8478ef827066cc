"""A stream of raw records scored into the archive: traffic files of kind
`ingest_stream`.

Records of `seq_len` tokens are made from the seed and scored `batch`
at a time through the program's serving prefill; every
`batches_per_append` batches go to the archive as one appended shard,
and the standing query (`standing_query`) catches up on it before the
next append starts. The deployment is a scorer feeding an archive
(`builders/ingest.py`).

After the window, every appended shard's sketch counts are compared
with a numpy histogram, the standing query's re-emission with
{A >= tau}, and `check_records` records drawn from the seed with the
float32 forward pass (`chipbench/llama_ref.py`).
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import deploy, plugins
from chipbench.harness import RESULT_TIMEOUT_S, log
from chipbench.reference import (Check, check_sample, logp_gap,
                                 reference_logp, set_mismatch,
                                 sketch_count_errors)


@dataclasses.dataclass
class AppendRecord:
    batches: List[int]             # token batch indices scored
    scores: np.ndarray             # what the prefill returned
    shard_id: int
    epoch: int
    t_start: float
    t_done: float
    error: Optional[str] = None
    t_scored: float = 0.0          # the prefill's scores on the host


def pick_records(records, traffic: dict, seed: int, k: int):
    """k scored records of the window, drawn from the seed, as
    (batch index, row, served score)."""
    batch = int(traffic["batch"])
    flat = [(a.batches[j // batch], j % batch, float(a.scores[j]))
            for a in records if a.error is None
            for j in range(a.scores.size)]
    return [flat[i] for i in check_sample(len(flat), k, seed, "records")]


class IngestStream:
    """Score records through the prefill and append them to the archive."""

    def __init__(self, dep, traffic: dict, seed: int):
        self.dep = dep
        self.traffic = traffic
        self.seed = int(seed)
        self.next_batch = 0
        self.appends: List[AppendRecord] = []
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench-ingest-")
        self.standing = None
        self.sink = None

    def close(self) -> None:
        self._tmp.cleanup()

    def tokens(self, batch_index: int):
        t = self.traffic
        return deploy.make_token_batch(
            self.seed, batch_index, int(t["batch"]), int(t["seq_len"]),
            int(self.dep.model["vocab_size"]), t["marker"],
            float(t["marker_rate"]))

    def _append_once(self) -> AppendRecord:
        from repro.data.pipeline import ScoreStore

        server = self.dep.archive.server
        per = int(self.traffic["batches_per_append"])
        idx = list(range(self.next_batch, self.next_batch + per))
        self.next_batch += per
        t_start = time.perf_counter()
        with TraceAnnotation("bench.prefill"):
            outs = [self.dep.prefill(self.dep.params,
                                     {"tokens": self.tokens(b)})
                    for b in idx]
            scores = np.concatenate([np.asarray(o, np.float32)
                                     for o in outs])
        t_scored = time.perf_counter()
        sq = self.standing
        walks = sq.emissions + sq.reemit_failures
        with TraceAnnotation("bench.append"):
            path = os.path.join(self._tmp.name, f"shard{len(self.appends)}")
            store = ScoreStore(path, scores.size, create=True)
            store.write(0, scores)
            epoch = server.append(store)
            # The standing query's epoch advances when its catch-up walk
            # is submitted; the walk has run when its count moves.
            deadline = time.monotonic() + RESULT_TIMEOUT_S
            while (sq.epoch < epoch
                   or sq.emissions + sq.reemit_failures == walks):
                if time.monotonic() > deadline:
                    raise TimeoutError("standing query did not catch up")
                time.sleep(0.0005)
        rec = AppendRecord(idx, scores, len(server.engine.shards) - 1, epoch,
                           t_start, time.perf_counter(), t_scored=t_scored)
        self.appends.append(rec)
        return rec

    def warm_up(self) -> None:
        """Certify the standing query, then one whole append cycle."""
        from repro.data.pipeline import IndexSink

        q = plugins.load("drivers", "closed_loop_queries").make_query(
            self.traffic["standing_query"])
        self.sink = IndexSink()
        self.standing = self.dep.archive.server.subscribe(
            q, key=deploy.stream_key(self.seed, "standing"), sink=self.sink)
        self.standing.wait_certified(timeout=RESULT_TIMEOUT_S)
        self._append_once()

    def window(self, seconds: float):
        """Append until `seconds` have passed; the window closes when the
        last append started in it is installed and caught up. Returns
        (appends in the window, t0, t_end)."""
        first = len(self.appends)
        t0 = time.perf_counter()
        while time.perf_counter() < t0 + seconds:
            try:
                self._append_once()
            except Exception as e:  # noqa: BLE001 — counted as failed
                now = time.perf_counter()
                self.appends.append(AppendRecord([], np.empty(0), -1, -1,
                                                 now, now, repr(e)))
        recs = self.appends[first:]
        done = [r for r in recs if r.error is None]
        for phase, ts in (("scoring", [r.t_scored - r.t_start
                                       for r in done]),
                          ("appending", [r.t_done - r.t_scored
                                         for r in done])):
            if ts:
                log(f"window: {phase} {sum(ts) / len(ts):.4f} s an append "
                    f"on average, {min(ts):.4f}-{max(ts):.4f} s")
        return recs, t0, max(r.t_done for r in recs)

    @staticmethod
    def attempted_failed(records):
        """Records attempted and failed: a failed append counts the
        records a whole append scores."""
        per = [len(a.scores) for a in records if a.error is None]
        size = max(per) if per else 0
        failed = sum(1 for a in records if a.error is not None)
        return len(records) * size, failed * size

    def check(self, cell, run) -> List[Check]:
        """Appended shards' sketch counts against numpy; the standing
        query's re-emission against {A >= tau}; a seeded sample of the
        window's scores against the float32 forward pass. The program's
        state is closed first, so the reference runs on a freed chip."""
        limits = cell.config["limits"]
        engine = self.dep.archive.engine
        appended = [a for a in self.appends if a.error is None]
        sketches = [type(s)(*(np.asarray(x) for x in s)) for s in
                    (engine.shard_sketches[a.shard_id] for a in appended)]
        tau = float(self.standing.tau)
        reemitted = [self.sink.indices(a.shard_id) for a in appended]
        picks = pick_records(run.records, self.traffic, self.seed,
                             int(self.traffic["check_records"]))
        num_bins = int(cell.config["archive"]["num_bins"])
        self.dep.close()
        self.close()

        count_err = sketch_count_errors([a.scores for a in appended],
                                        sketches, num_bins)
        standing = sum(set_mismatch(got, np.flatnonzero(a.scores >= tau))
                       for got, a in zip(reemitted, appended))
        ref = reference_logp(cell.config, self.traffic, self.seed,
                             [(b, r) for b, r, _ in picks])
        gap = logp_gap(np.asarray([s for _, _, s in picks]), ref)
        return [Check(name, float(value), float(limits[name]))
                for name, value in (("sketch_count_mismatch", count_err),
                                    ("standing_mismatch", standing),
                                    ("score_logp_gap", gap))]


def driver(dep, traffic: dict, seed: int) -> IngestStream:
    return IngestStream(dep, traffic, seed)


def control_readings(cell, seed: int, seconds: float) -> dict:
    """The sampled records' log-scores against the float32 reference,
    beside the reference computed with float8 (e4m3) matmul operands:
    `score_logp_gap`'s control."""
    dep = plugins.load("builders", cell.config["kind"]).build(cell.config,
                                                              seed)
    drv = driver(dep, cell.traffic, seed)
    drv.warm_up()
    recs, _, _ = drv.window(seconds)
    picks = pick_records(recs, cell.traffic, seed,
                         int(cell.traffic["check_records"]))
    dep.close()
    drv.close()
    at = [(b, r) for b, r, _ in picks]
    ref = reference_logp(cell.config, cell.traffic, seed, at)
    fp8 = reference_logp(cell.config, cell.traffic, seed, at, quantize=True)
    served = np.asarray([s for _, _, s in picks])
    return {"records": len(picks),
            "score_logp_gap": logp_gap(served, ref),
            "score_logp_gap_fp8_control": logp_gap(np.exp(fp8), ref),
            "served_logp_median": float(np.median(np.log(served)))}
