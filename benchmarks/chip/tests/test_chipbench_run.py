"""Whole runs of each cell at a size the CPU holds: the result line,
the traced run's reading, and the refusal without a chip."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_testlib import (HERE, INGEST, SOLO,  # noqa: E402
                               harness, load_cell, run_cell)


@pytest.mark.parametrize("workload", [SOLO, INGEST])
def test_a_run_prints_one_correct_result(workload, capsys, cpu_run):
    rc, res = run_cell(workload, capsys)
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in load_cell(workload).end_to_end}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])


def test_a_traced_run_reads_the_trace(capsys, cpu_run):
    rc, res = run_cell(SOLO, capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    dev = res["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] >= 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    per_layer = {m["name"] for m in load_cell(SOLO).per_layer}
    assert set(res["metrics"]) <= per_layer


def test_run_refuses_a_machine_with_no_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", SOLO,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == harness.NO_CHIP
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


