"""The record stream of `drivers/ingest_stream.py`, scored by a
DeepSeek-V2-architecture scorer: traffic files of kind
`ingest_stream_mla_moe`.

The load is `IngestStream`'s, unchanged. Only the check's reference
differs: the seeded records' served scores are compared with
`chipbench/deepseek_v2_ref.py`'s float32 forward pass on the same share
of the experts; the sketch counts and the standing query's re-emission
are compared exactly, as there.
"""
from __future__ import annotations

from typing import List

import numpy as np

from chipbench import deepseek_v2_ref, deploy, plugins
from chipbench.reference import (Check, logp_gap, set_mismatch,
                                 sketch_count_errors)

base = plugins.load("drivers", "ingest_stream")


def reference(cfg: dict, traffic: dict, seed: int, picks,
              precision: str = "float32", block: int = 8):
    """(log p(target), top-k expert ids (MoE layers, records, seq, k)) of
    the reference for records picked as (batch index, row), regenerated
    from the seed, in blocks of `block` records."""
    import jax.numpy as jnp

    w = deepseek_v2_ref.make_weights(cfg, seed, jnp.dtype(cfg["dtype"]))
    logp, ids = [], []
    for i in range(0, len(picks), block):
        part = picks[i:i + block]
        toks = np.stack([np.asarray(deploy.make_token_batch(
            seed, b, int(traffic["batch"]), int(traffic["seq_len"]),
            int(cfg["vocab_size"]), traffic["marker"],
            float(traffic["marker_rate"])))[r] for b, r in part])
        lp, top = deepseek_v2_ref.forward(w, toks, cfg,
                                          int(cfg["target_token"]),
                                          precision)
        logp.append(np.asarray(lp))
        ids.append(np.asarray(top))
    if not logp:
        return np.empty(0), np.empty(0)
    return np.concatenate(logp), np.concatenate(ids, axis=1)


def route_flips(ids_a, ids_b) -> float:
    """Share of (MoE layer, token) whose top-k expert sets differ."""
    a, b = np.sort(ids_a, axis=-1), np.sort(ids_b, axis=-1)
    return float(np.mean(np.any(a != b, axis=-1))) if a.size else 0.0


class IngestStreamMlaMoe(base.IngestStream):
    """`IngestStream` checked against the DeepSeek-V2 reference."""

    def check(self, cell, run) -> List[Check]:
        """As `IngestStream.check`, with the reference of this scorer."""
        limits = cell.config["limits"]
        engine = self.dep.archive.engine
        appended = [a for a in self.appends if a.error is None]
        sketches = [type(s)(*(np.asarray(x) for x in s)) for s in
                    (engine.shard_sketches[a.shard_id] for a in appended)]
        tau = float(self.standing.tau)
        reemitted = [self.sink.indices(a.shard_id) for a in appended]
        picks = base.pick_records(run.records, self.traffic, self.seed,
                                  int(self.traffic["check_records"]))
        num_bins = int(cell.config["archive"]["num_bins"])
        self.dep.close()
        self.close()

        count_err = sketch_count_errors([a.scores for a in appended],
                                        sketches, num_bins)
        standing = sum(set_mismatch(got, np.flatnonzero(a.scores >= tau))
                       for got, a in zip(reemitted, appended))
        ref, _ = reference(cell.config, self.traffic, self.seed,
                           [(b, r) for b, r, _ in picks])
        gap = logp_gap(np.asarray([s for _, _, s in picks]), ref)
        return [Check(name, float(value), float(limits[name]))
                for name, value in (("sketch_count_mismatch", count_err),
                                    ("standing_mismatch", standing),
                                    ("score_logp_gap", gap))]


def driver(dep, traffic: dict, seed: int) -> IngestStreamMlaMoe:
    return IngestStreamMlaMoe(dep, traffic, seed)


def control_readings(cell, seed: int, seconds: float) -> dict:
    """The sampled records' log-scores against the float32 reference,
    beside the reference computed with float8 (e4m3) matmul operands
    (`score_logp_gap`'s control), and the share of (MoE layer, token)
    whose top-k experts differ from the float32 reference's when its
    matmul operands are rounded to bfloat16, the served type."""
    dep = plugins.load("builders", cell.config["kind"]).build(cell.config,
                                                              seed)
    drv = driver(dep, cell.traffic, seed)
    drv.warm_up()
    recs, _, _ = drv.window(seconds)
    picks = base.pick_records(recs, cell.traffic, seed,
                              int(cell.traffic["check_records"]))
    dep.close()
    drv.close()
    at = [(b, r) for b, r, _ in picks]
    ref, ids = reference(cell.config, cell.traffic, seed, at)
    bf16, ids_bf16 = reference(cell.config, cell.traffic, seed, at,
                               "bfloat16")
    fp8, _ = reference(cell.config, cell.traffic, seed, at, "float8")
    served = np.asarray([s for _, _, s in picks])
    return {"records": len(picks),
            "score_logp_gap": logp_gap(served, ref),
            "score_logp_gap_bf16_reference": logp_gap(np.exp(bf16), ref),
            "score_logp_gap_fp8_control": logp_gap(np.exp(fp8), ref),
            "route_flip_share_bf16": route_flips(ids, ids_bf16),
            "served_logp_median": float(np.median(np.log(served)))}
