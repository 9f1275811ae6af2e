"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What is read, by name:

- device operations: the events of the line whose name starts with
  ``ops_line`` in every plane whose name starts with ``device_plane``
  (on a TPU, ``XLA Ops`` of ``/device:TPU:<n>``);
- program executions: events of the ``modules_line`` of those planes
  whose name contains a program's name (``serve_decode_chunk``);
- host spans: the events of the host plane, among them the harness's
  ``chipbench.*`` annotations, which carry their tick index as a stat.

Busy time is the union of the operation intervals on a device, inside
the traced window (the harness's ``chipbench.window`` span). Idle gaps
are the holes in that union, each put down to the innermost host event
of the harness's thread that covers most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds, trace clock

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


@dataclass(frozen=True)
class Layout:
    device_plane: str = "/device:TPU:"
    ops_line: str = "XLA Ops"
    modules_line: str = "XLA Modules"
    host_plane: str = "/host:CPU"
    host_thread: Optional[str] = None   # None: the line holding the window span


TPU = Layout()


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Timeline:
    window: Interval
    ops: Dict[str, List[Event]]         # device plane -> operations
    modules: Dict[str, List[Event]]     # device plane -> program executions
    host: List[Event]                   # the harness thread's events


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {trace_dir}")
    return found[0]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9,
                         dict(e.stats)))
    return out


def load(path: str, layout: Layout = TPU) -> Timeline:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host_lines: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith(layout.device_plane):
            for line in plane.lines:
                if line.name.startswith(layout.ops_line):
                    ops[plane.name] = _events(line)
                if line.name.startswith(layout.modules_line):
                    modules[plane.name] = _events(line)
        if plane.name == layout.host_plane:
            for line in plane.lines:
                if layout.host_thread is None or line.name == layout.host_thread:
                    host_lines.append(_events(line))
    host = next((evs for evs in host_lines
                 if any(e.name == "chipbench.window" for e in evs)), None)
    if host is None:
        raise ValueError(f"no chipbench.window span in {path}")
    win = next(e for e in host if e.name == "chipbench.window")
    return Timeline((win.start, win.end), ops, modules, host)


def union(intervals: Sequence[Interval], clip: Optional[Interval] = None
          ) -> List[Interval]:
    """Merged, sorted intervals, cut to ``clip``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if clip is not None:
            s, e = max(s, clip[0]), min(e, clip[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(tl: Timeline) -> Dict[str, List[Interval]]:
    """Per device plane, the merged intervals in which an operation ran."""
    return {p: union([(e.start, e.end) for e in evs], tl.window)
            for p, evs in tl.ops.items()}


def busy_s(tl: Timeline) -> Optional[float]:
    """Busy seconds averaged over the traced devices (None: no device)."""
    b = busy(tl)
    if not b:
        return None
    return sum(measure(v) for v in b.values()) / len(b)


def top_ops(tl: Timeline, n: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by operation name (an HLO instruction's name, without
    its text after " = "), summed over devices, averaged per device,
    largest first."""
    tot: Dict[str, float] = defaultdict(float)
    for evs in tl.ops.values():
        for e in evs:
            s, t = max(e.start, tl.window[0]), min(e.end, tl.window[1])
            if t > s:
                tot[e.name.split(" = ", 1)[0]] += t - s
    k = max(1, len(tl.ops))
    return sorted(((name, v / k) for name, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]


def _attribute(host: List[Event], gaps: List[Interval]) -> List[str]:
    """For each gap (sorted), the innermost host event that covers more
    than half of it. Such an event holds the gap's midpoint, and the
    events of one thread nest, so one sweep with a stack finds it."""
    evs = sorted(host, key=lambda e: (e.start, -e.end))
    out, stack, i = [], [], 0
    for s, e in gaps:
        mid = 0.5 * (s + e)
        while i < len(evs) and evs[i].start <= mid:
            while stack and stack[-1].end < evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end < mid:
            stack.pop()
        name = "host: no span"
        for ev in reversed(stack):
            if min(ev.end, e) - max(ev.start, s) > 0.5 * (e - s):
                name = ev.name
                break
        out.append(name)
    return out


def idle_gaps(tl: Timeline, n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds on the first device plane, summed by what the host
    was doing, largest first."""
    if not tl.ops:
        return []
    plane = sorted(tl.ops)[0]
    gaps = subtract([tl.window], busy(tl)[plane])
    tot: Dict[str, float] = defaultdict(float)
    for (s, e), name in zip(gaps, _attribute(tl.host, gaps)):
        tot[name] += e - s
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def executions(tl: Timeline, program: str) -> List[Event]:
    """Executions of ``program`` on the first device plane, in order."""
    if not tl.modules:
        return []
    plane = sorted(tl.modules)[0]
    return sorted((e for e in tl.modules[plane] if program in e.name),
                  key=lambda e: e.start)


def per_span_device_s(tl: Timeline, span: str, program: str
                      ) -> Dict[int, float]:
    """Device seconds of ``program`` executions that start inside each
    host span named ``span``, keyed by the span's ``tick`` stat. Spans
    cut by the window's edges are left out."""
    spans = sorted((e for e in tl.host if e.name == span
                    and tl.window[0] <= e.start and e.end <= tl.window[1]),
                   key=lambda e: e.start)
    runs = executions(tl, program)
    out: Dict[int, float] = {}
    j = 0
    for sp in spans:
        while j < len(runs) and runs[j].start < sp.start:
            j += 1
        total = 0.0
        while j < len(runs) and runs[j].start <= sp.end:
            total += runs[j].end - runs[j].start
            j += 1
        out[int(sp.stats["tick"])] = total
    return out


def exposed_collective_s(tl: Timeline) -> float:
    """Seconds of collective operations during which no other operation
    runs on the same device, averaged over devices."""
    per = []
    for evs in tl.ops.values():
        coll = [(e.start, e.end) for e in evs
                if any(m in e.name for m in COLLECTIVE_MARKS)]
        comp = [(e.start, e.end) for e in evs
                if not any(m in e.name for m in COLLECTIVE_MARKS)]
        per.append(measure(subtract(union(coll, tl.window),
                                    union(comp, tl.window))))
    return sum(per) / len(per) if per else 0.0
