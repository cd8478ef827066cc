"""Shared by the chip benchmark's tests: paths, tiny sizes, one run."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

from chipbench import harness  # noqa: E402

SOLO, INGEST = "archive.rt.solo", "ingest.smollm-360m"
TINY_ARCHIVE = {"records": 120_000, "chunk_records": 16384, "workers": 2}
TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 256}
SEED = 2 ** 33 + 7


def load_cell(workload):
    return harness.load_cell(workload)


def tiny(workload):
    """(config override, traffic override) that shrink a cell."""
    if workload == INGEST:
        cfg = load_cell(INGEST).config
        return ({**TINY_MODEL, "archive": {**cfg["archive"],
                                           **TINY_ARCHIVE}},
                {"seq_len": 64, "batch": 8, "batches_per_append": 2,
                 "check_records": 8})
    return TINY_ARCHIVE, None


def run_cell(workload, capsys, trace=0):
    """One run at the tiny size; returns (exit code, result line)."""
    co, to = tiny(workload)
    rc = harness.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", "1.5", "--trace", str(trace)],
                      require_chip=False, config_override=co,
                      traffic_override=to)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])


