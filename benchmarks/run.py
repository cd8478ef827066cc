"""Benchmark suite entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  paper_figures  — Figs 1/5/6/7/8/9/10/12 + Table 4 reproduction numbers
  bench_kernels  — per-kernel allclose + reference timings
  roofline       — per-(arch x shape) roofline terms from results/dryrun.json
                   (skipped silently if the dry-run artifact is absent)

``--json PATH`` additionally writes every captured row to a
machine-readable trajectory file (CI uploads it as the BENCH_PR10.json
artifact per commit; ``--fast --json`` is the quick tier CI runs, covering
engine cold-build at 1/4/8 workers, draw_sample throughput, the run_many
batch, threshold_select throughput at 1e6/1e7 records, the live-plane
rows — incremental ingestion vs rebuild-per-append and standing-query
lag — and the durability rows: fsync'd journal-append overhead and
journal-replay recovery of a 1e6-record corpus).
``--baseline PATH`` diffs the captured rows against a committed trajectory
file (the repo carries ``BENCH_PR10.json``) and prints a per-row delta
table, so every CI run shows its drift from the checked-in baseline.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import platform
import re
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

_ROW_RE = re.compile(r"^([A-Za-z0-9_.-]+),([-+0-9.eE]+)(?:,(.*))?$")


def _parse_rows(text: str):
    """Parse ``name,us_per_call[,derived]`` CSV rows out of bench output."""
    rows = []
    for line in text.splitlines():
        m = _ROW_RE.match(line.strip())
        if m:
            rows.append({"name": m.group(1),
                         "us_per_call": float(m.group(2)),
                         "derived": m.group(3) or ""})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow statistical sweeps")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write captured rows as a machine-readable "
                         "trajectory file (e.g. BENCH_PR8.json)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed trajectory file to diff against; "
                         "prints a per-row delta table after the run")
    args = ap.parse_args()
    enable_compile_cache()

    baseline_rows = {}
    if args.baseline:
        # Read up front: --json may legitimately overwrite the same path.
        try:
            with open(args.baseline) as f:
                baseline_rows = {r["name"]: r
                                 for r in json.load(f).get("rows", [])}
        except (OSError, ValueError, KeyError) as e:
            print(f"baseline {args.baseline} unreadable ({e}); "
                  "skipping delta table", file=sys.stderr)

    from benchmarks import (bench_durable, bench_kernels, bench_live,
                            bench_serve, paper_figures)

    benches = []
    if not args.fast:
        benches += [(f.__name__, f) for f in paper_figures.ALL]
    else:
        benches += [("bench_failure_precision",
                     paper_figures.bench_failure_precision),
                    ("bench_recall_target",
                     paper_figures.bench_recall_target)]
    benches += [(f.__name__, f) for f in bench_kernels.ALL]
    benches += [(f.__name__, f) for f in bench_serve.ALL]
    benches += [(f.__name__, f) for f in bench_live.ALL]
    benches += [(f.__name__, f) for f in bench_durable.ALL]

    failed = []
    rows = []
    t_start = time.time()
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                fn()
        except Exception:  # noqa: BLE001
            sys.stdout.write(buf.getvalue())
            traceback.print_exc()
            failed.append(name)
            continue
        out = buf.getvalue()
        sys.stdout.write(out)
        rows += _parse_rows(out)

    try:
        from benchmarks import roofline
        import pathlib
        if pathlib.Path("results/dryrun.json").exists():
            roofline.main()
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failed.append("roofline")

    if baseline_rows:
        width = max((len(r["name"]) for r in rows), default=4) + 2
        print(f"\n== delta vs {args.baseline} (negative = faster) ==")
        print(f"{'name':<{width}}{'base_us':>12}{'now_us':>12}{'delta':>9}")
        for r in rows:
            base = baseline_rows.get(r["name"])
            if base is None or base["us_per_call"] <= 0:
                print(f"{r['name']:<{width}}{'(new)':>12}"
                      f"{r['us_per_call']:>12.0f}{'':>9}")
                continue
            delta = (r["us_per_call"] / base["us_per_call"] - 1.0) * 100.0
            print(f"{r['name']:<{width}}{base['us_per_call']:>12.0f}"
                  f"{r['us_per_call']:>12.0f}{delta:>+8.1f}%")
        gone = sorted(set(baseline_rows) - {r["name"] for r in rows})
        if gone:
            print(f"rows missing vs baseline: {gone}")

    if args.json:
        import jax
        payload = {
            "schema_version": 1,
            "suite": "fast" if args.fast else "full",
            "wall_seconds": round(time.time() - t_start, 3),
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "failed": failed,
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {len(rows)} rows -> {args.json}")

    if failed:
        print(f"FAILED benchmarks: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
