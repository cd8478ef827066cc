"""The controls behind each limit of `correct`, run by hand on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 10 \
        --seeds 11 12 13

For every seed it builds the cell, warms it up and runs a short window
at the cell's own load, as a benchmark run does, then prints one JSON
line with the program's readings and the control's on the same answers:
`control_readings` of the cell's traffic kind (`drivers/<kind>.py`)
says which.

The benchmark's own runs never run this; PERF.md records its readings.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import argparse  # noqa: E402
import json  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    from chipbench import harness, plugins

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    if harness.chip_devices(cell.chips) is None:
        print("control: no TPU", file=sys.stderr)
        return harness.NO_CHIP
    read = plugins.load("drivers", cell.traffic["kind"]).control_readings
    for seed in args.seeds:
        out = read(cell, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
