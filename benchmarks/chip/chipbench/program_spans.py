"""The program's own spans in a kept trace, and what they name.

The program under test opens `jax.profiler.TraceAnnotation` spans named
`supg.*` inside its query and append paths (docs/architecture.md,
"Ops note: reading a server trace"). They land in the same `.xplane.pb` as the
device ops and the benchmark's `bench.*` spans, on the same clock.
`chipbench/trace.py` reads the `bench.*` spans only; this module reads
the `supg.*` ones, with their arguments and the thread that opened them,
and reduces them:

* per-request sums of one span name, by the request id `q`;
* the device-idle time of the window that no program span covers;
* idle gaps named by the innermost span of either kind;
* for each long idle gap inside a `bench.prefill` span, the program
  spans open on other threads during it.

A run keeps its trace with `run.py ... --trace 1 --trace-dir DIR`;
`spans_report.py DIR` prints `report` as one JSON line.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace
from chipbench.stats import percentile

PREFIX = "supg."
# (name, start_s, end_s, arguments, thread: the event's line in the host
# plane; the program's threads all carry the process's name)
Span = Tuple[str, float, float, Dict[str, int], int]

QUERY_PARTS = ("supg.sample", "supg.drain_wait", "supg.bound", "supg.emit")
STALL_S = 0.05


def load(path: str) -> List[Span]:
    """Every `supg.*` event of the host plane, sorted by start."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9,
                                {k: v for k, v in ev.stats}, line_no))
    out.sort(key=lambda sp: sp[1])
    return out


def within(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    """The spans that start inside [lo, hi]."""
    return [sp for sp in spans if lo <= sp[1] <= hi]


def requests(spans: Sequence[Span]) -> List[int]:
    """Request ids admitted by the server, in order."""
    return [int(sp[3]["q"]) for sp in spans if sp[0] == "supg.admit"]


def per_request(spans: Sequence[Span], name: str) -> Dict[int, float]:
    """Seconds of span `name` summed by its request id."""
    out: Dict[int, float] = {}
    for n, s, e, args, _ in spans:
        if n == name and "q" in args:
            out[int(args["q"])] = out.get(int(args["q"]), 0.0) + (e - s)
    return out


def mean_per_request(spans: Sequence[Span], name: str) -> Optional[float]:
    """Mean over the admitted requests of span `name`'s seconds; spans
    without a request id (`supg.drain_wait`) are divided evenly, which is
    exact where one request runs at a time."""
    qs = requests(spans)
    if not qs:
        return None
    by_q = per_request(spans, name)
    if by_q:
        return sum(by_q.get(q, 0.0) for q in qs) / len(qs)
    return total(spans, name) / len(qs)


def total(spans: Sequence[Span], name: str) -> float:
    return sum(e - s for n, s, e, _, _ in spans if n == name)


def mean_per_event(spans: Sequence[Span], name: str) -> Optional[float]:
    """Mean seconds of one `name` span (one `supg.append` per append)."""
    times = [e - s for n, s, e, _, _ in spans if n == name]
    return sum(times) / len(times) if times else None


def idle(ops: Sequence[trace.Event], window: Tuple[float, float]
         ) -> List[Tuple[float, float]]:
    """The stretches of the window in which no device op ran."""
    lo, hi = window
    busy = trace.union([(max(s, lo), min(e, hi)) for _, s, e in ops
                        if e > lo and s < hi])
    out, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def uncovered_s(gaps: Sequence[Tuple[float, float]],
                spans: Sequence[Span]) -> float:
    """Seconds of `gaps` (sorted, disjoint) that no span covers, on any
    thread."""
    cover = trace.union([(s, e) for _, s, e, _, _ in spans])
    covered, j = 0.0, 0
    for g0, g1 in gaps:
        while j < len(cover) and cover[j][1] <= g0:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < g1:
            covered += min(cover[k][1], g1) - max(cover[k][0], g0)
            k += 1
    return sum(g1 - g0 for g0, g1 in gaps) - covered


def named_gaps(gaps: Sequence[Tuple[float, float]],
               bench: Sequence[trace.Event], spans: Sequence[Span],
               top: int = 10) -> List[List]:
    """The longest gaps, each named by the innermost span of either kind
    open at its midpoint (`trace.py`'s rule over both lists)."""
    both = list(bench) + [(n, s, e) for n, s, e, _, _ in spans]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [[trace._attribute(g, both), g[1] - g[0]] for g in longest]


def stalls(gaps: Sequence[Tuple[float, float]],
           bench: Sequence[trace.Event], spans: Sequence[Span],
           inside: str = "bench.prefill", min_s: float = STALL_S
           ) -> List[dict]:
    """Each idle gap of at least `min_s` that overlaps an `inside` span
    (`inside_s` of it does), with the program spans open during it
    (name, thread, seconds of overlap)."""
    outer = [(s, e) for n, s, e in bench if n == inside]
    out = []
    for g0, g1 in gaps:
        if g1 - g0 < min_s:
            continue
        within_s = sum(max(0.0, min(e, g1) - max(s, g0)) for s, e in outer)
        if within_s <= 0.0:
            continue
        open_ = [[n, th, min(e, g1) - max(s, g0)]
                 for n, s, e, _, th in spans if s < g1 and e > g0]
        out.append({"start_s": g0, "gap_s": g1 - g0, "inside_s": within_s,
                    "open": open_})
    return out


def report(path: str) -> dict:
    """What one kept trace says of the program: each query part's mean
    per request, the sampling parallelism, the idle time no program span
    explains, the longest gaps named, the append time and the stalls."""
    ops, bench, planes = trace.load(path)
    summary = trace.summarize(ops, bench, devices=max(len(planes), 1))
    window = next((s, e) for n, s, e in bench if n == trace.WINDOW_SPAN)
    spans = within(load(path), *window)
    bench = summary.spans
    gaps = idle(ops, window)
    idle_s = sum(e - s for s, e in gaps)
    phases = trace.span_phases(summary, "bench.query", "threshold_select")
    out: dict = {"window_s": summary.window_s, "idle_s": idle_s,
                 "requests": len(requests(spans)),
                 "pre_emission_s": trace.mean([p for p, _ in phases]),
                 "emission_s": trace.mean([p for _, p in phases]),
                 "bench_idle_gaps": trace.breakdown(summary)["idle_gaps"]}
    for name in QUERY_PARTS + ("supg.sample.rng", "supg.emit.stitch"):
        out[name] = mean_per_request(spans, name)
    chunk_s, sample_s = total(spans, "supg.sample.chunk"), total(
        spans, "supg.sample")
    out["sample_chunk_s"], out["sample_s"] = chunk_s, sample_s
    out["sample_parallelism"] = chunk_s / sample_s if sample_s else None
    out["idle_unexplained_share"] = (
        100.0 * uncovered_s(gaps, spans) / idle_s if idle_s else None)
    out["append_s"] = mean_per_event(spans, "supg.append")
    out["appends"] = sum(1 for sp in spans if sp[0] == "supg.append")
    # The end-to-end numbers as this traced run read them, for the cost
    # of tracing: client latency (each `bench.query` is one query's
    # submit to result) and records appended over the window.
    lat = [e - s for n, s, e in bench if n == "bench.query"]
    out["query_p50_s_traced"] = percentile(lat, 50) if lat else None
    out["records_per_s_traced"] = sum(
        sp[3].get("records", 0) for sp in spans
        if sp[0] == "supg.append") / summary.window_s
    out["idle_gaps"] = named_gaps(gaps, bench, spans)
    out["stalls"] = stalls(gaps, bench, spans)
    out["append_stalls"] = stalls(gaps, bench, spans, inside="bench.append")
    longest = stalls(gaps, bench, spans, min_s=0.0)
    out["longest_prefill_gap"] = max(longest, key=lambda g: g["gap_s"],
                                     default=None)
    return out
