"""Analysts in closed loops: traffic files of kind `closed_loop_queries`.

`clients` analysts each send their next SUPG query only when the last
one has answered. Each walks `pattern` (names of entries in `queries`)
in blocks, shuffled per block from the seed, so every seed sends the
same mix in another order; each query gets its own key from the seed.
The deployment is an archive (`builders/archive.py`).

After the window, every answer's recall is held against the true
labels, and `check_answers` answers drawn from the seed, the largest
always among them, are compared record by record with {A >= tau} plus
their labeled positives.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import deploy, plugins
from chipbench.harness import RESULT_TIMEOUT_S, log
from chipbench.reference import (Check, RecallOracle, binomial_limit,
                                 check_sample, recall_misses, reference_set,
                                 set_mismatch)


def make_query(spec: dict):
    """The program's recall-target query from a traffic file's entry."""
    from repro.core.queries import SUPGQuery

    if spec["kind"] != "rt":
        raise ValueError(f"query kind {spec['kind']!r}: this driver sends "
                         f"recall-target (rt) queries only")
    return SUPGQuery(target="recall", gamma=spec["gamma"],
                     delta=spec["delta"], budget=spec["budget"],
                     method=spec.get("method", "is"),
                     weight_scheme=spec.get("weight_scheme", "sqrt"))


@dataclasses.dataclass(eq=False)
class QueryRecord:
    client: int
    index: int
    gamma: float
    t_submit: float
    t_done: float = math.nan
    error: Optional[str] = None
    tau: float = math.nan
    positives: Optional[np.ndarray] = None
    selected: int = 0
    selection: object = None       # the ShardedSelection, read after

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


def client_kinds(pattern, seed: int, client: int, block: int) -> list:
    """The queries client `client` sends in its `block`-th pass over the
    pattern: the same multiset every time, in a seeded order."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), client, block])
    return list(rng.permutation(np.asarray(pattern)))


def emitted(selection, offsets: np.ndarray) -> np.ndarray:
    """An answer's records as global indices."""
    return np.concatenate([selection.indices(sh) + offsets[sh]
                           for sh in range(selection.num_shards)])


def answer_mismatch(scores, answers) -> int:
    """Records by which the sampled answers differ from the reference
    computed over `scores` (the control passes them rounded)."""
    return sum(set_mismatch(got, reference_set(scores, q.tau, q.positives))
               for q, got in answers)


class ClosedLoopQueries:
    """Clients in closed loops against one `SelectionServer`."""

    def __init__(self, dep, traffic: dict, seed: int):
        self.dep = dep
        self.traffic = traffic
        self.seed = int(seed)
        self.queries = {k: make_query(v)
                        for k, v in traffic["queries"].items()}
        self.key = deploy.stream_key(seed, "queries")

    def _key(self, client: int, index: int):
        return jax.random.fold_in(self.key, client * 1_000_003 + index)

    def warm_up(self) -> None:
        """One query of each entry."""
        warm = deploy.stream_key(self.seed, "warmup")
        for i, (name, q) in enumerate(sorted(self.queries.items())):
            t = time.perf_counter()
            self.dep.server.submit(q, key=jax.random.fold_in(warm, i)).result(
                timeout=RESULT_TIMEOUT_S)
            log(f"setup: warm-up {name} query {time.perf_counter() - t:.3f} s")

    def window(self, seconds: float):
        """Run the clients for `seconds`; each finishes its last query.
        Returns (records, t0, t_end)."""
        server = self.dep.server
        pattern = self.traffic["pattern"]
        t0 = time.perf_counter()
        t_end = t0 + seconds
        records: List[List[QueryRecord]] = [
            [] for _ in range(int(self.traffic["clients"]))]

        def client(c: int) -> None:
            names: list = []
            i = 0
            while time.perf_counter() < t_end:
                if not names:
                    names = client_kinds(pattern, self.seed, c,
                                         i // len(pattern))
                name = str(names.pop(0))
                rec = QueryRecord(c, i, self.traffic["queries"][name]["gamma"],
                                  time.perf_counter())
                try:
                    with TraceAnnotation("bench.query"):
                        rec.t_submit = time.perf_counter()
                        sel = server.submit(self.queries[name],
                                            tenant=f"tenant{c}",
                                            key=self._key(c, i)).result(
                            timeout=RESULT_TIMEOUT_S)
                        rec.t_done = time.perf_counter()
                    rec.tau = float(sel.tau)
                    rec.positives = np.asarray(sel.sampled_positive_global)
                    rec.selected = int(sel.total_selected)
                    rec.selection = sel
                except Exception as e:  # noqa: BLE001 — counted as failed
                    rec.t_done = time.perf_counter()
                    rec.error = f"{type(e).__name__}: {e}"
                records[c].append(rec)
                i += 1

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(len(records))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        flat = sorted((r for rs in records for r in rs),
                      key=lambda r: r.t_submit)
        return flat, t0, t_end

    @staticmethod
    def attempted_failed(records):
        return len(records), sum(1 for q in records if q.error is not None)

    def gather_answers(self, records):
        """The answered queries, and a seeded sample of them (the largest
        answer always among them) with their emitted records. Drops every
        other answer's records."""
        done = [q for q in records if q.error is None]
        sample = [done[i] for i in check_sample(
            len(done), int(self.traffic["check_answers"]), self.seed,
            "checkrt")]
        if done:
            largest = max(done, key=lambda q: q.selected)
            if all(q is not largest for q in sample):
                sample.append(largest)
        answers = [(q, emitted(q.selection, self.dep.offsets))
                   for q in sample]
        for q in records:
            q.selection = None
        return done, answers

    def delta(self) -> float:
        return max(float(s["delta"]) for s in self.traffic["queries"].values())

    def check(self, cell, run) -> List[Check]:
        """Every answer's recall against the true labels, and the sampled
        answers record by record against {A >= tau} plus positives. The
        server is closed first, so the references run on a freed chip."""
        done, answers = self.gather_answers(run.records)
        self.dep.close()
        dep = self.dep
        misses = recall_misses(done, RecallOracle(dep.scores, dep.labels))
        mismatch = answer_mismatch(dep.scores, answers)
        return [Check("emitted_mismatch", float(mismatch),
                      float(cell.config["limits"]["emitted_mismatch"])),
                Check("target_misses", float(misses),
                      float(binomial_limit(len(done), self.delta())))]


def driver(dep, traffic: dict, seed: int) -> ClosedLoopQueries:
    return ClosedLoopQueries(dep, traffic, seed)


def control_readings(cell, seed: int, seconds: float) -> dict:
    """The program's readings beside its controls', on one deployment:
    the sampled answers against the reference computed over bfloat16-
    rounded scores (`emitted_mismatch`'s control), and a second window
    in which every query skips SUPG's confidence correction (the
    program's own `noci` path: `target_misses`'s control)."""
    import jax.numpy as jnp

    dep = plugins.load("builders", cell.config["kind"]).build(cell.config,
                                                              seed)
    drv = driver(dep, cell.traffic, seed)
    drv.warm_up()
    recs, _, _ = drv.window(seconds)
    done, answers = drv.gather_answers(recs)
    noci = dict(cell.traffic, queries={
        k: dict(v, method="noci") for k, v in cell.traffic["queries"].items()})
    ndrv = driver(dep, noci, seed + 1)
    ndrv.warm_up()
    nrecs, _, _ = ndrv.window(seconds)
    for q in nrecs:
        q.selection = None
    dep.close()
    oracle = RecallOracle(dep.scores, dep.labels)
    ndone = [q for q in nrecs if q.error is None]
    low = np.asarray(jnp.asarray(dep.scores).astype(jnp.bfloat16))
    return {
        "queries": len(done), "checked": len(answers),
        "emitted_mismatch": answer_mismatch(dep.scores, answers),
        "emitted_mismatch_bf16_control": answer_mismatch(low, answers),
        "target_misses": recall_misses(done, oracle),
        "target_limit": binomial_limit(len(done), drv.delta()),
        "noci_queries": len(ndone),
        "target_misses_noci_control": recall_misses(ndone, oracle),
        "target_limit_noci": binomial_limit(len(ndone), drv.delta()),
    }

