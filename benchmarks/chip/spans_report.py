"""What the program's own spans say of one kept trace.

    python3 benchmarks/chip/run.py --workload archive.rt.solo --seed 7 \
        --seconds 51 --trace 1 --trace-dir /tmp/trace7
    python3 benchmarks/chip/spans_report.py /tmp/trace7

Prints one JSON line: `chipbench/program_spans.py`'s `report` (each
query part's mean per request, the sampling parallelism, the idle time
no `supg.*` span covers, the longest idle gaps named by the innermost
span, the mean append and the long idle gaps inside `bench.prefill`).
The benchmark's own runs never run this; PERF.md records its readings.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import json  # noqa: E402

from chipbench import program_spans, trace  # noqa: E402


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0]
    if Path(path).is_dir():
        path = trace.find_xplane(path)
    print(json.dumps(program_spans.report(path)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
