"""The MoE combine kernel's roofline reader on hand-made traces of the
DeepSeek-V2-Lite ingest cell."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_testlib import load_cell  # noqa: E402

from chipbench import plugins, trace  # noqa: E402
from chipbench.harness import Run  # noqa: E402

CELL = "ingest.dsv2-lite"


def test_combine_roofline_reads_held_rows_and_output_over_kernel_time():
    """`moe_combine_roofline`: nothing without `moe_combine` events; on a
    stub trace, 26 MoE layers a record of 512 tokens, each reading its
    0.75 held rows a token in bf16 and writing a float32 row of 2,048, at
    819 GB/s over the kernel's 50 ms."""
    records = [types.SimpleNamespace(error=None,
                                     scores=np.zeros(128, np.float32))]
    run = Run(load_cell(CELL), "TPU v5 lite", 1.0, 0.0, 0.0, 1.0, 1.0,
              records=records)
    read = plugins.load("metrics", "moe_combine_roofline").read
    assert read(run) is None                          # no trace
    window = [("bench.window", 0.0, 1.0)]
    run.trace = trace.summarize([("jit_serve_prefill/moe_gmm.1", 0.0, 0.5)],
                                window)
    assert read(run) is None                          # no combine kernel
    run.trace = trace.summarize(
        [("jit_serve_prefill/moe_combine.3", 0.0, 0.025),
         ("jit_serve_prefill/moe_combine.3", 0.5, 0.525)], window)
    per_record = 26 * 512 * 2048 * (0.75 * 2 + 4)
    assert read(run) == pytest.approx(
        100 * 128 * per_record / 819e9 / 0.050)
