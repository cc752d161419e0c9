"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The device plane of each chip (``/device:TPU:<n>``) carries one event per
executed HLO op on its ``XLA Ops`` line, named by the op's HLO text
(``%flash_fwd.7 = (...) custom-call(...)``); a Pallas kernel's op takes
the name of its ``pallas_call``. A control-flow op (the layer scan's
``while``) spans the ops of its body, so an op's own time is its
duration less that of the ops it contains. The host plane carries the
benchmark's own spans (``jax.profiler.TraceAnnotation``): ``bench.window``
around the timed loop, ``bench.batch`` around the batch it hands the
runner, ``bench.step`` around the step call. Only what falls inside
``bench.window`` is counted.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.batch", "bench.step")
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\.\d+$")
_HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")

# (name, start_ns, duration_ns)
Event = Tuple[str, float, float]


@dataclasses.dataclass
class Summary:
    window_s: float                   # length of bench.window
    busy_s: float                     # union of device-op intervals, mean over chips
    op_s: Dict[str, float]            # own device seconds per op, summed over chips
    op_n: Dict[str, int]              # events per op name
    idle_gaps: List[Tuple[str, float]]  # longest gaps, labelled by host span
    chips: int

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of one kernel: every op named ``kernel`` or
        ``kernel.<n>``."""
        return sum(s for name, s in self.op_s.items()
                   if base_name(name) == kernel)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def base_name(op: str) -> str:
    return _SUFFIX.sub("", op)


def op_name(event_name: str) -> str:
    """``%flash_fwd.7 = (...) custom-call(...)`` -> ``flash_fwd.7``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def _own_times(intervals: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, start, end) -> (name, end - start less the time of the
    intervals directly nested in it)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][1], -intervals[i][2]))
    nested = [0.0] * len(intervals)
    stack: List[int] = []
    for i in order:
        _, a, b = intervals[i]
        while stack and intervals[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= intervals[stack[-1]][2]:
            nested[stack[-1]] += b - a
        stack.append(i)
    return [(name, max(b - a - nested[i], 0.0))
            for i, (name, a, b) in enumerate(intervals)]


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _label(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The host span that overlaps the gap the most."""
    best, best_overlap = "outside bench spans", 0.0
    for name, start, dur in spans:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce_events(device: Sequence[Sequence[Event]],
                  host: Sequence[Event],
                  n_gaps: int = 10) -> Summary:
    """``device``: the op events of each chip; ``host``: the benchmark's
    host spans, ``bench.window`` among them. Times in ns on one clock."""
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]
    spans = [e for e in host if e[0] in HOST_SPANS]
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    for events in device:
        clipped = []
        for name, start, dur in events:
            a, b = max(start, w0), min(start + dur, w1)
            if b > a:
                clipped.append((op_name(name), a, b))
        for name, own in _own_times(clipped):
            op_s[name] = op_s.get(name, 0.0) + own * 1e-9
            op_n[name] = op_n.get(name, 0) + 1
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    chips = max(len(device), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * 1e-9 / chips,
        op_s=op_s, op_n=op_n,
        idle_gaps=[(_label(g, spans), (g[1] - g[0]) * 1e-9)
                   for g in gaps[:n_gaps]],
        chips=chips)


def load(trace_dir: str, n_gaps: int = 10) -> Summary:
    """Reduce the one ``.xplane.pb`` written under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {paths}")
    data = ProfileData.from_file(paths[0])
    device: List[List[Event]] = []
    host: List[Event] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            events: Optional[List[Event]] = None
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events]
            if events is None:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line")
            device.append(events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name == WINDOW_SPAN or e.name in HOST_SPANS]
    if not device:
        raise ValueError("the trace has no TPU device plane")
    return reduce_events(device, host, n_gaps)
