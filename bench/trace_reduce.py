"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

The window is the host span ``bench.window`` that the harness opens at the
start of the measured window and closes at its end. Device time is the
union of the op intervals on each chip's ``XLA Ops`` line, clipped to the
window and averaged over the chips. An idle gap is a stretch of the window
in which no op ran; each is labelled by the benchmark's own host span
(``bench.*``) that overlaps it most, else by the host event that does,
else ``none``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]           # (start_ns, end_ns)


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    thread: str = ""


@dataclasses.dataclass
class Trace:
    """Events of one trace: per chip, its op spans; and the host spans."""
    device_ops: Dict[str, List[Span]]
    host: List[Span]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                                  # averaged over chips
    device_ops: List[Tuple[str, float]]            # top ops, seconds
    idle_gaps: List[Tuple[str, float]]             # longest gaps, labelled
    n_devices: int


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Span(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Span(e.name, e.start_ns, e.end_ns, line.name)
                            for e in line.events if e.duration_ns > 0)
    return Trace(device_ops, host)


def union(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` clipped to ``window``."""
    lo, hi = window
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    out: List[Interval] = []
    for a, b in clipped:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that the disjoint sorted ``busy`` leaves."""
    out, cur = [], window[0]
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def op_name(hlo: str) -> str:
    """``%reduce_window_sum.90 = f32[...] reduce-window(...)`` ->
    ``reduce_window_sum``."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def label(gap: Interval, host: Sequence[Span]) -> str:
    """What the host was doing in ``gap`` (see the module docstring)."""
    mine: Dict[str, float] = {}
    python: Dict[str, float] = {}
    for s in host:
        if s.name == WINDOW_SPAN:
            continue
        ov = _overlap(gap, (s.start, s.end))
        if ov <= 0:
            continue
        if s.name.startswith(BENCH_PREFIX):
            mine[s.name] = mine.get(s.name, 0.0) + ov
        elif s.thread.startswith("python"):
            python[s.name] = max(python.get(s.name, 0.0), ov)
    if mine:
        name, ov = max(mine.items(), key=lambda kv: kv[1])
        if ov >= 0.5 * (gap[1] - gap[0]):
            return name
    if python:
        return max(python.items(), key=lambda kv: kv[1])[0]
    return "none"


def window_of(trace: Trace) -> Optional[Interval]:
    spans = [s for s in trace.host if s.name == WINDOW_SPAN]
    if not spans:
        return None
    s = max(spans, key=lambda s: s.end - s.start)
    return (s.start, s.end)


def summarize(trace: Trace, window: Optional[Interval] = None,
              top: int = 10) -> Optional[Summary]:
    """``None`` when the trace holds no window or no device op in it."""
    window = window or window_of(trace)
    if window is None or not trace.device_ops:
        return None
    busy_ns, per_op = 0.0, {}
    longest: List[Interval] = []
    for ops in trace.device_ops.values():
        busy = union(((s.start, s.end) for s in ops), window)
        busy_ns += sum(b - a for a, b in busy)
        for s in ops:
            d = _overlap((s.start, s.end), window)
            if d > 0:
                name = op_name(s.name)
                per_op[name] = per_op.get(name, 0.0) + d
        longest.extend(gaps(busy, window))
    n = len(trace.device_ops)
    if busy_ns <= 0:
        return None
    longest.sort(key=lambda g: g[0] - g[1])
    labelled = [(label(g, trace.host), (g[1] - g[0]) * 1e-9)
                for g in longest[:top]]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(window[1] - window[0]) * 1e-9,
                   busy_s=busy_ns / n * 1e-9,
                   device_ops=[(k, v / n * 1e-9) for k, v in ops],
                   idle_gaps=labelled, n_devices=n)
