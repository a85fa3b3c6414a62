"""Reduce a profiler trace to what the per-layer metrics read.

Input is anything shaped like ``jax.profiler.ProfileData``: planes with a
``name`` and ``lines``, lines with a ``name`` and ``events``, events with a
``name``, ``start_ns`` and ``duration_ns``.  Device planes are those named
``/device:TPU:<n>``; on each, the line of XLA operations gives the busy
intervals and the line of XLA modules gives whole-program executions.  Host
spans are the events of the host plane whose names are in
``hooks.SPANS``, written by ``jax.profiler.TraceAnnotation``.

- busy: the union of the operation intervals inside the window, averaged
  over the devices;
- idle share: 1 - busy / window;
- idle gaps: each stretch of the window in which no operation runs on a
  device, split by the innermost host span that covers it (``"other"``
  where none does), summed by span name;
- device ops: total device time per operation name;
- modules: the durations of each whole-program execution, by name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    modules: Dict[str, List[float]] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def _innermost(spans: Sequence[Tuple[float, float, str]]
               ) -> Tuple[List[float], List[Optional[str]]]:
    """Cut points and, for each stretch from one cut to the next (the last
    one open-ended, and one before the first), the name of the innermost
    (latest-starting) span covering it."""
    points = sorted([(b, 0, i) for i, (a, b, _) in enumerate(spans)]
                    + [(a, 1, i) for i, (a, b, _) in enumerate(spans)])
    cuts: List[float] = [float("-inf")]
    labels: List[Optional[str]] = [None]
    active: Dict[int, Tuple[float, float, str]] = {}
    i = 0
    while i < len(points):
        t = points[i][0]
        while i < len(points) and points[i][0] == t:
            _, starts, k = points[i]
            if starts:
                active[k] = spans[k]
            else:
                active.pop(k, None)
            i += 1
        cuts.append(t)
        labels.append(max(active.values(), key=lambda s: s[0])[2]
                      if active else None)
    return cuts, labels


def _attribute(gaps, cuts, labels) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        t, i = a, bisect.bisect_right(cuts, a) - 1
        while t < b:
            hi = min(cuts[i + 1], b) if i + 1 < len(cuts) else b
            out[labels[i] or "other"] += hi - t
            t, i = hi, i + 1
    return out


def reduce_planes(planes, span_names: Sequence[str],
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> Reduction:
    """Reduce trace planes; ``window`` is (start_ns, end_ns), or taken from
    the host span named ``"window"``."""
    spans: List[Tuple[float, float, str]] = []
    devices: List[Tuple[list, list]] = []
    for plane in planes:
        if _DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops = [(op_name(e.name), e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == _MODULES_LINE:
                    mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if window is None:
        marks = [(a, b) for a, b, n in spans if n == "window"]
        if not marks:
            raise ValueError("the trace holds no 'window' span")
        window = max(marks, key=lambda ab: ab[1] - ab[0])
    lo, hi = window
    inner = [s for s in spans if s[2] != "window"]
    cuts, labels = _innermost(inner)

    busy = 0.0
    gaps_by: Dict[str, float] = defaultdict(float)
    ops_by: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(list)
    for ops, mods in devices:
        merged = _union(_clip([(a, b) for _, a, b in ops], lo, hi))
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, secs in _attribute(gaps, cuts, labels).items():
            gaps_by[name] += secs
        for name, a, b in ops:
            if lo <= a and b <= hi:
                ops_by[name] += b - a
        for name, a, b in mods:
            if lo <= a and b <= hi:
                modules[name].append((b - a) * 1e-9)
    n = len(devices)
    rank = lambda d: sorted(((k, v * 1e-9 / n) for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                     devices=n, idle_gaps=rank(gaps_by),
                     device_ops=rank(ops_by), modules=dict(modules))


def load(trace_dir: str):
    """The planes of the newest ``.xplane.pb`` under ``trace_dir``, read
    once into lists (the profiler's own are single-pass iterators)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    return [_Plane(p.name, [_Line(l.name, [_Event(e.name, e.start_ns,
                                                   e.duration_ns)
                                            for e in l.events])
                            for l in p.lines])
            for p in data.planes]


@dataclass
class _Event:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class _Line:
    name: str
    events: List[_Event]


@dataclass
class _Plane:
    name: str
    lines: List[_Line]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")

