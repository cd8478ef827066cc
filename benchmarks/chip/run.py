"""Run one cell of the chip benchmark; see chipbench/harness.py.

    python3 benchmarks/chip/run.py --workload archive.rt.solo \
        --seed 7 --seconds 10 --trace 0
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
