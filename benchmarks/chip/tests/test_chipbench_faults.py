"""A run whose timed path is broken underneath must read not correct:
an answer altered where it is produced, half a batch left out."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_testlib import INGEST, SOLO, run_cell  # noqa: E402


def _drop_one(real):
    def threshold_select(scores, tau, **kw):
        out = real(scores, tau, **kw)
        return out[:-1] if out.size else out
    return threshold_select


@pytest.mark.parametrize("workload", [SOLO])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        workload, capsys, monkeypatch, cpu_run):
    from repro.kernels.threshold_select import ops
    monkeypatch.setattr(ops, "threshold_select",
                        _drop_one(ops.threshold_select))
    rc, res = run_cell(workload, capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["emitted_mismatch"]["value"] > 0


def _broken_prefill(how):
    from repro.launch import serve

    real = serve.make_serve_prefill

    def make(cfg, target_token=1):
        fn = real(cfg, target_token)

        def prefill(params, batch):
            s = fn(params, batch)
            if how == "half":       # half the batch left out: its mean
                half = s.shape[0] // 2
                return s.at[half:].set(s[:half].mean())
            return s * 1.5           # every score altered
        return prefill
    return make


@pytest.mark.parametrize("how", ["half", "altered"])
def test_a_broken_scorer_is_not_correct(how, capsys, monkeypatch,
                                        cpu_run):
    from repro.launch import serve
    monkeypatch.setattr(serve, "make_serve_prefill", _broken_prefill(how))
    rc, res = run_cell(INGEST, capsys)
    assert rc == 0 and res["correct"] is False
    gap = res["checks"]["score_logp_gap"]
    assert gap["value"] > gap["limit"]


