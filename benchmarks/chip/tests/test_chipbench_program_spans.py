"""The program's `supg.*` spans in a kept trace: loaded with their
arguments and threads and kept apart from the benchmark's `bench.*`
spans; reduced to per-request times, unexplained idle time, named gaps
and stalls on hand-made spans; read from a tiny traced run; and, on the
recorded v5e trace (which has no `supg.*` event), every accepted reader
and the breakdown pinned to the values they gave before the program had
spans."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_testlib import (INGEST, SEED, SOLO, harness,  # noqa: E402
                               load_cell, tiny)

from chipbench import plugins, program_spans, trace  # noqa: E402
from chipbench.harness import Run  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "solo_v5e.xplane.pb"

# Two queries in a 10-second window, each: admit, sample (two chunk
# resolves on pool threads under the first), drain wait, bound, emit.
SPANS = [
    ("supg.admit", 0.0, 0.001, {"q": 1, "queued_us": 3}, 0),
    ("supg.sample", 0.1, 2.1, {"q": 1, "draws": 3000, "chunks": 2}, 0),
    ("supg.sample.chunk", 0.2, 1.2, {"q": 1, "shard": 0, "chunk": 0}, 2),
    ("supg.sample.chunk", 0.2, 1.0, {"q": 1, "shard": 1, "chunk": 4}, 3),
    ("supg.drain_wait", 2.2, 2.3, {"records": 3000}, 0),
    ("supg.bound", 2.4, 2.6, {"q": 1}, 0),
    ("supg.emit", 2.7, 3.9, {"q": 1, "walks": 1, "spans": 24}, 0),
    ("supg.append", 1.0, 1.006, {"records": 256, "shards": 1}, 4),
    ("supg.append", 2.0, 2.010, {"records": 256, "shards": 1}, 4),
    ("supg.admit", 5.0, 5.001, {"q": 2, "queued_us": 2}, 0),
    ("supg.sample", 5.1, 6.1, {"q": 2, "draws": 3000, "chunks": 2}, 0),
    ("supg.drain_wait", 6.2, 6.5, {"records": 3000}, 0),
    ("supg.bound", 6.6, 6.7, {"q": 2}, 0),
    ("supg.emit", 6.8, 8.8, {"q": 2, "walks": 1, "spans": 24}, 0),
]
BENCH = [("bench.query", 0.0, 4.0), ("bench.query", 5.0, 9.0)]
OPS = [("p/threshold_select_rows", 3.0, 3.5),
       ("p/threshold_select_rows", 7.0, 8.0)]
WINDOW = (0.0, 10.0)


def reading(name):
    """Each planned per-layer reading, from the hand-made spans."""
    gaps = program_spans.idle(OPS, WINDOW)
    if name == "idle_unexplained_share":
        return 100.0 * program_spans.uncovered_s(gaps, SPANS) / sum(
            e - s for s, e in gaps)
    if name == "append_s":
        return program_spans.mean_per_event(SPANS, "supg.append")
    return program_spans.mean_per_request(SPANS, name)


@pytest.mark.parametrize("name,want", [
    ("supg.sample", (2.0 + 1.0) / 2),
    ("supg.drain_wait", (0.1 + 0.3) / 2),
    ("supg.bound", (0.2 + 0.1) / 2),
    ("supg.emit", (1.2 + 2.0) / 2),
    # idle 0-3, 3.5-7 and 8-10 (8.5 s); spans leave 0.399, 1.499 and
    # 1.2 s of it uncovered
    ("idle_unexplained_share", 100.0 * (0.399 + 1.499 + 1.2) / 8.5),
    ("append_s", (0.006 + 0.010) / 2),
])
def test_readings_of_hand_made_spans(name, want):
    assert reading(name) == pytest.approx(want)


def test_per_request_sums_and_requests():
    assert program_spans.requests(SPANS) == [1, 2]
    assert program_spans.per_request(SPANS, "supg.sample.chunk") == {
        1: pytest.approx(1.8)}
    assert program_spans.within(SPANS, 4.5, 10.0)[0][3]["q"] == 2
    assert program_spans.mean_per_request([], "supg.sample") is None
    assert program_spans.mean_per_event(SPANS, "supg.round") is None


def test_gap_attribution_prefers_the_innermost_program_span():
    gap = (0.3, 0.9)          # inside bench.query, sample and both chunks
    assert trace._attribute(gap, BENCH) == "bench.query"
    named = program_spans.named_gaps([(4.2, 4.7), gap, (9.2, 9.3)], BENCH,
                                     SPANS)
    assert named == [["supg.sample.chunk", pytest.approx(0.6)],
                     ["host", pytest.approx(0.5)],     # between queries
                     ["host", pytest.approx(0.1)]]


def test_stalls_name_the_program_spans_open_during_them():
    bench = [("bench.prefill", 0.0, 2.0), ("bench.append", 2.0, 2.5)]
    spans = [("supg.round", 0.4, 0.8, {}, 1), ("supg.append", 2.1, 2.2,
                                               {}, 4)]
    gaps = [(0.5, 0.7), (1.0, 1.01), (2.6, 2.9)]    # the last: no prefill
    got = program_spans.stalls(gaps, bench, spans)
    assert got == [{"start_s": 0.5, "gap_s": pytest.approx(0.2),
                    "inside_s": pytest.approx(0.2),
                    "open": [["supg.round", 1, pytest.approx(0.2)]]}]
    quiet = program_spans.stalls([(0.9, 1.0)], bench, spans)
    assert quiet == [{"start_s": 0.9, "gap_s": pytest.approx(0.1),
                      "inside_s": pytest.approx(0.1), "open": []}]
    # A gap that straddles the prefill's end counts, with the part inside.
    edge = program_spans.stalls([(1.95, 2.15)], bench, spans)
    assert edge[0]["inside_s"] == pytest.approx(0.05)
    assert edge[0]["open"] == [["supg.append", 4, pytest.approx(0.05)]]
    appends = program_spans.stalls([(1.95, 2.15)], bench, spans,
                                    inside="bench.append")
    assert appends[0]["inside_s"] == pytest.approx(0.15)


def test_a_tiny_traced_run_keeps_program_spans_apart(tmp_path, capsys,
                                                     cpu_run):
    co, to = tiny(SOLO)
    rc = harness.main(["--workload", SOLO, "--seed", str(SEED),
                       "--seconds", "1.5", "--trace", "1",
                       "--trace-dir", str(tmp_path)],
                      require_chip=False, config_override=co,
                      traffic_override=to)
    capsys.readouterr()
    assert rc == 0
    path = trace.find_xplane(str(tmp_path))
    _, bench, _ = trace.load(path)
    assert bench and all(n.startswith("bench.") for n, _, _ in bench)
    spans = program_spans.load(path)
    names = {sp[0] for sp in spans}
    assert set(program_spans.QUERY_PARTS) <= names
    sample = next(sp for sp in spans if sp[0] == "supg.sample")
    assert sample[3]["q"] > 0 and sample[3]["draws"] == 3000
    assert all(isinstance(sp[4], int) for sp in spans)
    rep = program_spans.report(path)
    assert rep["requests"] > 0
    for name in program_spans.QUERY_PARTS:
        assert rep[name] > 0, name
    assert 0.0 <= rep["idle_unexplained_share"] <= 100.0
    assert rep["append_s"] is None and rep["stalls"] == []
    assert rep["longest_prefill_gap"] is None   # no ingest in this cell


# The accepted readers on the recorded trace, before the program had
# spans: three queries, each selecting 10^6 records (solo); four appends
# of 256 records over a 3-second window (ingest).
PINNED = {
    (SOLO, "pre_emission_s.solo"): 0.9089555376666668,
    (SOLO, "emission_s.solo"): 0.12147007433333339,
    (SOLO, "threshold_select_roofline"): 2.232854363578041,
    (SOLO, "device_idle_share.solo"): 97.85504280433632,
    (INGEST, "ingest.mfu"): 58.61976303106599,
    (INGEST, "serve_prefill_roofline"): None,
    (INGEST, "score_hist_roofline"): None,
    (INGEST, "device_idle_share.ingest"): 97.85504280433632,
}


def recorded_summary():
    ops, spans, planes = trace.load(str(RECORDED))
    return trace.summarize(ops, spans, devices=len(planes))


@pytest.mark.parametrize("workload,name", sorted(PINNED))
def test_accepted_readers_read_the_recorded_trace_as_before(workload,
                                                            name):
    cell = load_cell(workload)
    assert name in {m["name"] for m in cell.per_layer}
    if workload == SOLO:
        records = [types.SimpleNamespace(error=None, selected=1_000_000)
                   for _ in range(3)]
    else:
        records = [types.SimpleNamespace(error=None,
                                         scores=np.zeros(256, np.float32))
                   for _ in range(4)]
    run = Run(cell, "TPU v5 lite", 3.0, 20.0, 0.0, 3.0, 3.0,
              records=records, trace=recorded_summary())
    got = plugins.load("metrics", name).read(run)
    want = PINNED[(workload, name)]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


def test_recorded_breakdown_as_before_and_no_program_spans():
    b = trace.breakdown(recorded_summary())
    assert b["device_ops"][0] == [
        "jit_threshold_select_rows/threshold_select_rows.1",
        pytest.approx(0.06599921499999906, rel=1e-12)]
    assert [n for n, _ in b["idle_gaps"]] == ["bench.query"] * 10
    assert [t for _, t in b["idle_gaps"][:3]] == [
        pytest.approx(0.992889956, rel=1e-12),
        pytest.approx(0.851537129, rel=1e-12),
        pytest.approx(0.828315896, rel=1e-12)]
    assert program_spans.load(str(RECORDED)) == []
    rep = program_spans.report(str(RECORDED))
    assert rep["idle_gaps"] == rep["bench_idle_gaps"] == b["idle_gaps"]
    assert rep["idle_unexplained_share"] == pytest.approx(100.0)
