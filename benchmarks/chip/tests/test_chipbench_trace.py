"""The trace reduction: busy time, op times and idle gaps by host span,
on hand-made events and on a small trace recorded on a TPU v5e."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import plugins, trace  # noqa: E402
from chipbench.harness import Run  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "solo_v5e.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_summary_of_hand_made_events():
    ops = [("threshold_select_rows", 1.0, 2.0),
           ("fusion.1", 1.5, 2.5),          # overlaps: busy counts once
           ("threshold_select_rows", 6.0, 7.0),
           ("outside", 20.0, 21.0)]          # after the window
    spans = [("bench.window", 0.0, 10.0),
             ("bench.query", 0.5, 8.0),
             ("bench.oracle", 3.0, 5.5)]
    s = trace.summarize(ops, spans)
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(2.5)
    assert s.kernel_seconds("threshold_select") == pytest.approx(2.0)
    assert s.op_seconds["fusion.1"] == pytest.approx(1.0)
    gaps = dict((round(t, 6), n) for n, t in s.gaps)
    assert gaps[3.5] == "bench.oracle"        # 2.5..6.0, mostly oracle
    assert gaps[3.0] == "bench.query"         # 7.0..10.0
    assert gaps[1.0] == "bench.query"         # 0.0..1.0
    assert sum(t for _, t in s.gaps) == pytest.approx(10.0 - 2.5)
    b = trace.breakdown(s, top=2)
    assert b["device_ops"][0] == ["threshold_select_rows", 2.0]
    assert len(b["idle_gaps"]) == 2


def test_idle_share_and_query_phases_of_hand_made_events():
    ops = [("p/threshold_select_rows", 2.0, 2.5),
           ("p/threshold_select_rows", 3.0, 3.5),
           ("p/threshold_select_rows", 7.0, 7.5)]
    spans = [("bench.window", 0.0, 10.0),
             ("bench.query", 0.0, 4.0),
             ("bench.query", 5.0, 9.0),
             ("bench.query", 9.0, 9.9)]          # no kernel: left out
    s = trace.summarize(ops, spans)
    assert trace.idle_share(s) == pytest.approx(85.0)
    assert trace.idle_share(None) is None
    assert trace.span_phases(s, "bench.query", "threshold_select") == [
        pytest.approx((2.0, 2.0)), pytest.approx((2.0, 2.0))]
    assert trace.span_phases(None, "bench.query", "x") == []
    assert trace.mean([1.0, 3.0]) == 2.0 and trace.mean([]) is None


def test_program_time_counts_nested_ops_once():
    ops = [("jit_serve_prefill/while.1", 1.0, 5.0),
           ("jit_serve_prefill/fusion.2", 1.5, 2.0),     # inside the loop
           ("jit_serve_prefill/copy.3", 5.5, 6.0),
           ("jit_serve_prefill_other/x", 6.0, 9.0)]      # another program
    s = trace.summarize(ops, [("bench.window", 0.0, 10.0)])
    assert s.program_busy_s("jit_serve_prefill") == pytest.approx(4.5)


def test_prefill_roofline_reads_useful_flops_over_program_time():
    class Rec:
        error = None
        scores = np.zeros(64, np.float32)

    model = {"hidden_size": 64, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 128, "vocab_size": 256}
    cell = harness_cell(model, {"seq_len": 32})
    ops = [("jit_serve_prefill/while.1", 0.0, 1e-6)]
    run = Run(cell, "TPU v5 lite", 1.0, 0.0, 0.0, 1.0, 1.0, records=[Rec()])
    read = plugins.load("metrics", "serve_prefill_roofline").read
    assert read(run) is None                   # no trace, nothing to read
    run.trace = trace.summarize(ops, [("bench.window", 0.0, 1.0)])
    from chipbench import work
    want = (64 * work.llama_prefill_flop_per_record(model, 32) / 197e12
            / 1e-6 * 100)
    assert read(run) == pytest.approx(want)


def harness_cell(config, traffic):
    from chipbench.harness import Cell
    return Cell("c", 1, config, traffic, [], [])


def test_busy_time_is_averaged_over_devices():
    ops = [("a", 0.0, 1.0), ("a", 0.0, 1.0)]
    s = trace.summarize(ops, [("bench.window", 0.0, 2.0)], devices=2)
    assert s.busy_s == pytest.approx(0.5)


def test_a_window_is_required():
    with pytest.raises(RuntimeError):
        trace.summarize([], [])


def test_reduction_of_a_recorded_solo_trace():
    """Three RT queries over 10^8 records on one v5e (3-second window):
    each walks 24 chunks, so 72 emission kernels run, plus the padding
    of each shard's short last chunk inside the same program."""
    ops, spans, planes = trace.load(str(RECORDED))
    assert planes == ["/device:TPU:0"]
    s = trace.summarize(ops, spans, devices=len(planes))
    assert s.window_s == pytest.approx(3.0994, abs=1e-3)
    assert len(s.spans_named("bench.query")) == 3
    kernel = "jit_threshold_select_rows/threshold_select_rows.1"
    assert max(s.op_seconds, key=s.op_seconds.get) == kernel
    assert sum(1 for n, _, _ in s.ops if n == kernel) == 72
    assert len(s.kernel_events("threshold_select")) == 84
    assert 0 < s.kernel_seconds("threshold_select") <= s.busy_s
    assert s.busy_s / s.window_s == pytest.approx(0.0214, abs=1e-3)
    assert {n for n, _ in s.gaps} <= {"bench.query", "bench.oracle", "host"}
    assert sum(t for _, t in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    phases = trace.span_phases(s, "bench.query", "threshold_select")
    assert len(phases) == 3
    for (pre, post), (_, qs, qe) in zip(phases,
                                        s.spans_named("bench.query")):
        assert pre > post > 0 and pre + post == pytest.approx(qe - qs)
