"""The controls, at a size the CPU holds: the reference computed one
precision lower, and the program's own uncorrected path, each fail the
limit that the program's answers meet."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402
from chipbench_testlib import (INGEST, SEED, SOLO, TINY_ARCHIVE,  # noqa: E402
                               load_cell, tiny)

from chipbench import plugins  # noqa: E402


def control_readings(cell, seconds):
    return plugins.load("drivers", cell.traffic["kind"]).control_readings(
        cell, SEED, seconds)


def test_query_controls_fail_their_limits(cpu_run):
    cell = load_cell(SOLO)
    cell.config = {**cell.config, **TINY_ARCHIVE}
    out = control_readings(cell, 1.5)
    assert out["emitted_mismatch"] == 0
    assert out["emitted_mismatch_bf16_control"] > 0
    assert out["target_misses"] <= out["target_limit"]
    assert out["target_misses_noci_control"] > out["target_limit_noci"]


def test_scorer_control_fails_its_limit(cpu_run):
    cell = load_cell(INGEST)
    co, to = tiny(INGEST)
    cell.config = {**cell.config, "archive": co["archive"],
                   "num_hidden_layers": 4}      # published widths
    cell.traffic = {**cell.traffic, **to, "seq_len": 128,
                    "check_records": 16}
    out = control_readings(cell, 1.5)
    limit = cell.config["limits"]["score_logp_gap"]
    assert out["score_logp_gap"] <= limit
    assert out["score_logp_gap_fp8_control"] > limit
    assert np.isfinite(out["served_logp_median"])
