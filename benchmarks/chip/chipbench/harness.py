"""One run of one benchmark cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration file (`configs/`), a traffic file
(`traffic/`) and, through `BENCHMARK.json`, the metrics it reports. The
harness finds the code for each by name (`chipbench/plugins.py`): the
configuration's `kind` is built by `builders/<kind>.py`, the traffic's
`kind` driven and checked by `drivers/<kind>.py`, each metric read by
`metrics/<name>.py`. The run builds the deployment from the seed, warms
up every shape the traffic uses (set-up), measures for `--seconds`,
then checks the answers against the plain references.
Its last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `checks`, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]
CACHE_DIR = BENCH_DIR / ".jax_cache"
NO_CHIP = 3
RESULT_TIMEOUT_S = 300.0


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # the configuration file, as run
    traffic: dict                 # the traffic file
    end_to_end: List[dict]        # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_cell(name: str) -> Cell:
    """A cell of `BENCHMARK.json` with its files and metric entries."""
    spec = load_json(REPO / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in reported else [])]
    return Cell(name, int(w["chips"]), load_json(REPO / conf["file"]),
                load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
                e2e, per_layer)


def use_compile_cache() -> str:
    """JAX's persistent cache at $JAX_COMPILATION_CACHE_DIR, else at a
    fixed path in the checkout; every program is kept, however fast it
    compiled, so later runs load the small ones too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chip_devices(chips: int):
    """The accelerator devices, or None where there are too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return devs


@dataclasses.dataclass
class Run:
    """What a metric reader reads: one run's window and its counts."""
    cell: Cell
    device_kind: str
    seconds: float                # the window asked for
    setup_s: float
    t0: float
    t_end: float                  # t0 + seconds
    t_close: float                # the last work of the window finished
    records: list = dataclasses.field(default_factory=list)  # the driver's
    trace: object = None          # trace.Summary with --trace 1


def read_metrics(entries: List[dict], run: Run) -> dict:
    """Each entry's reader; a reader that finds nothing is left out."""
    from chipbench import plugins

    out = {}
    for m in entries:
        value = plugins.load("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, t_start: Optional[float] = None,
         require_chip: bool = True,
         config_override: Optional[dict] = None,
         traffic_override: Optional[dict] = None) -> int:
    """Run one cell; returns the exit code. The keyword arguments exist
    for the tests: they skip the look for a chip and shrink the sizes."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of a chip cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed)")
    args = ap.parse_args(argv)

    import repro  # noqa: F401 — the system under test; fails fast without it

    cell = load_cell(args.workload)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if traffic_override:
        cell.traffic = {**cell.traffic, **traffic_override}
    cache = use_compile_cache()

    import jax

    devs = jax.devices()
    log(f"setup: jax and its devices ready at "
        f"{time.perf_counter() - t_start:.3f} s")
    if require_chip and chip_devices(cell.chips) is None:
        log(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devs)} {devs[0].platform!r} device(s)")
        return NO_CHIP
    dev = devs[0]
    log(f"chipbench: {args.workload} seed {args.seed} on {dev.platform} "
        f"{dev.device_kind} x{len(devs)}; compile cache {cache}")

    from chipbench import plugins
    from chipbench import trace as tracelib
    from chipbench.compiles import CompileCounter

    builder = plugins.load("builders", cell.config["kind"])
    drivers = plugins.load("drivers", cell.traffic["kind"])
    with CompileCounter() as cc:
        t = time.perf_counter()
        dep = builder.build(cell.config, args.seed)
        log(f"setup: deployment built in {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        drv = drivers.driver(dep, cell.traffic, args.seed)
        drv.warm_up()
        log(f"setup: warm-up in {time.perf_counter() - t:.3f} s")
        setup_s = time.perf_counter() - t_start
        log(f"setup: {setup_s:.3f} s, {cc.acquired} executables acquired "
            f"({cc.compiled} compiled, {cc.cache_hits} from the cache, "
            f"{cc.seconds:.3f} s)")
        acquired, traced, collected = cc.acquired, cc.traced, len(
            cc.gc_pauses)
        trace_dir = None
        if args.trace:
            trace_dir = args.trace_dir or tempfile.mkdtemp(
                prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # TraceMe spans only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            recs, t0, t_end = drv.window(args.seconds)
            t_close = time.perf_counter()
        if args.trace:
            jax.profiler.stop_trace()
        in_window = cc.acquired - acquired
        traced = cc.traced - traced
        pauses = cc.gc_pauses[collected:]

    run = Run(cell, dev.device_kind, args.seconds, setup_s, t0, t_end,
              t_close, records=recs)
    log(f"window: {len(recs)} {cell.traffic['kind']} records in "
        f"{t_close - t0:.3f} s; {in_window} executables acquired in the "
        f"window {cc.names[len(cc.names) - in_window:]}, {traced} jaxprs "
        f"traced")
    if pauses:
        longest = max(pauses, key=lambda p: p[1])
        log(f"window: {len(pauses)} garbage collections took "
            f"{sum(p[1] for p in pauses):.4f} s, the longest "
            f"{longest[1]:.4f} s (generation {longest[0]}); "
            f"{sum(1 for p in pauses if p[0] == 2)} of generation 2")

    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs) if stats else 0
    attempted, failed = drv.attempted_failed(recs)
    checks = drv.check(cell, run)

    metrics = {}
    extra = {}
    if args.trace:
        path = tracelib.find_xplane(trace_dir)
        log(tracelib.describe(path))
        ops, spans, planes = tracelib.load(path)
        run.trace = tracelib.summarize(ops, spans, devices=max(
            len(planes), 1))
        extra["breakdown"] = tracelib.breakdown(run.trace)
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = read_metrics(cell.per_layer, run)
    else:
        metrics = read_metrics(cell.end_to_end, run)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    correct = failed == 0 and all(c.ok for c in checks)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **extra,
              "checks": {c.name: c.as_json() for c in checks}}
    for c in checks:
        log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0
