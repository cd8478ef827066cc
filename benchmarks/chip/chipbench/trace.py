"""From a profiler trace to device busy time, kernel time and idle gaps.

`load` reads the `.xplane.pb` that `jax.profiler` writes and returns two
flat lists on one clock (seconds): the operations that ran on the
device, and the benchmark's own host spans (`bench.*`, written with
`jax.profiler.TraceAnnotation`). `summarize` is pure arithmetic over
those lists, so the tests check it on a small recorded trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_s, end_s)

DEVICE_OP_LINE = "XLA Ops"
DEVICE_MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(log_dir: str) -> str:
    """The one `.xplane.pb` under a `jax.profiler.trace` directory."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CPU")


def op_name(hlo: str) -> str:
    """`%threshold_select_rows.1 = s32[...] custom-call(...)` ->
    `threshold_select_rows.1`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """`jit_threshold_select_rows(7290613353789583569)` ->
    `jit_threshold_select_rows`."""
    return event_name.split("(", 1)[0]


def _name_ops(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Prefix each op with the program (XLA module) running around it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][2] >= s:
            name = f"{modules[i][0]}/{name}"
        out.append((name, s, e))
    return out


def load(path: str) -> Tuple[List[Event], List[Event], List[str]]:
    """(device ops, host spans, names of the device planes that ran ops)
    from one xplane file.

    Device ops are the events of each device plane's "XLA Ops" line,
    named `<program>/<op>` (`jit_threshold_select_rows/
    threshold_select_rows.1`); several device planes are merged (the
    caller divides busy time by their count). Host spans are every
    `bench.*` event of the host plane, whatever thread wrote it.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Event] = []
    spans: List[Event] = []
    devices: List[str] = []
    for plane in data.planes:
        if _is_device_plane(plane.name):
            plane_ops, modules = [], []
            for line in plane.lines:
                if line.name not in (DEVICE_OP_LINE, DEVICE_MODULE_LINE):
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if line.name == DEVICE_OP_LINE:
                        plane_ops.append((op_name(ev.name), s, e))
                    else:
                        modules.append((module_name(ev.name), s, e))
            if plane_ops:           # a device that ran something
                devices.append(plane.name)
                ops += _name_ops(plane_ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return ops, spans, devices


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


@dataclasses.dataclass
class Summary:
    """What one traced window reads."""
    window_s: float
    busy_s: float                     # union of device ops, per device
    op_seconds: Dict[str, float]      # device time by operation name
    ops: List[Event]                  # device ops inside the window
    spans: List[Event]                # bench.* host spans (window left out)
    gaps: List[Tuple[str, float]]     # idle gaps, longest first, by span

    def kernel_seconds(self, kernel: str) -> float:
        """Device time of every operation whose name contains `kernel`."""
        return sum(t for n, t in self.op_seconds.items() if kernel in n)

    def kernel_events(self, kernel: str) -> List[Event]:
        return [ev for ev in self.ops if kernel in ev[0]]

    def spans_named(self, name: str) -> List[Event]:
        return sorted((ev for ev in self.spans if ev[0] == name),
                      key=lambda ev: ev[1])

    def program_busy_s(self, program: str) -> float:
        """Device time of one compiled program (XLA module): the union of
        its ops, so ops nested in a loop count once."""
        return sum(e - s for s, e in union(
            [(s, e) for n, s, e in self.ops
             if n.startswith(program + "/")]))


def idle_share(summary: Optional[Summary]) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)


def span_phases(summary: Optional[Summary], span: str, kernel: str
                ) -> List[Tuple[float, float]]:
    """For each host span `span` inside which `kernel` ran: (time from the
    span's start to the kernel's first op, time from there to the span's
    end). Meaningful where one span is open at a time."""
    if summary is None:
        return []
    starts = [s for _, s, _ in summary.kernel_events(kernel)]
    out = []
    for _, s, e in summary.spans_named(span):
        inside = [k for k in starts if s <= k <= e]
        if inside:
            out.append((min(inside) - s, e - min(inside)))
    return out


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def _attribute(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The innermost host span open at the gap's midpoint (a nested span,
    the oracle inside a query, names its gap); where none is, the span
    that covers most of the gap; "host" where no span touches it."""
    mid = 0.5 * (gap[0] + gap[1])
    at_mid = [(e - s, name) for name, s, e in spans if s <= mid <= e]
    if at_mid:
        return min(at_mid)[1]
    best, best_cover = "host", 0.0
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def summarize(ops: Sequence[Event], spans: Sequence[Event],
              window: Optional[Tuple[float, float]] = None,
              devices: int = 1) -> Summary:
    """Reduce one trace to its window's busy time, op times and gaps.

    The window is the `bench.window` span unless given. Busy time is the
    union of device-op intervals inside it, divided by `devices`; an idle
    gap is a stretch of the window with no device op, named by the host
    span that covers most of it.
    """
    if window is None:
        wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if len(wins) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                               f"{len(wins)}")
        window = wins[0]
    lo, hi = window
    inside = _clip(ops, lo, hi)
    busy = union([(s, e) for _, s, e in inside])
    op_seconds: Dict[str, float] = {}
    for n, s, e in inside:
        op_seconds[n] = op_seconds.get(n, 0.0) + (e - s)
    host = [ev for ev in spans if ev[0] != WINDOW_SPAN]
    gaps = []
    t = lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((_attribute((t, s), host), s - t))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=hi - lo,
                   busy_s=sum(e - s for s, e in busy) / max(devices, 1),
                   op_seconds=op_seconds, ops=inside,
                   spans=_clip(host, lo, hi), gaps=gaps)


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The result line's `breakdown`: the device ops that took most time
    and the longest idle gaps, each by name, in seconds."""
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in summary.gaps[:top]]}


def describe(path: str, limit: int = 8) -> str:
    """A few lines on the trace's planes, lines and first op names, for
    the run's standard error: enough to see what the reduction read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        out.append(f"trace plane {plane.name}: {lines[:limit]}")
        if _is_device_plane(plane.name):
            for ln in plane.lines:
                if ln.name == DEVICE_OP_LINE:
                    names = sorted({op_name(ev.name) for ev in ln.events})
                    out.append(f"trace ops ({len(names)} names): "
                               f"{names[:4 * limit]}")
    return "\n".join(out)
