"""Profiler traces reduced to events, and the reductions the per-layer
metrics share.

An event is (plane, line, name, start_ns, dur_ns) on the profiler's one
clock. Device operations are the events on a device plane's ``XLA Ops``
line; the harness's own host spans are the host events named
``bench.*``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|psum|allgather|allreduce|reducescatter", re.I)
HOST_SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: str) -> List[Event]:
    """Device op events and the harness's host spans from an .xplane.pb."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


# ------------------------------------------------------------ intervals
def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged: List[List[float]], lo: float, hi: float):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: List[List[float]], b: List[List[float]]) -> List[List[float]]:
    """Parts of the merged set ``a`` not covered by the merged set ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ------------------------------------------------------------ the view
class TraceView:
    """The events of one traced window, cut to the host's round spans."""

    def __init__(self, events: List[Event], round_span: str = "bench.round"):
        self.events = events
        rounds = [e for e in events if e.name == round_span]
        if not rounds:
            raise ValueError(f"no {round_span!r} span in the trace")
        self.rounds = len(rounds)
        self.lo = min(e.start_ns for e in rounds)
        self.hi = max(e.end_ns for e in rounds)
        self.planes = sorted({e.plane for e in events
                              if DEVICE_PLANE.match(e.plane)})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self, plane: Optional[str] = None) -> List[Event]:
        """Device ops inside the window, of one plane or of all."""
        return [e for e in self.events
                if DEVICE_PLANE.match(e.plane)
                and (plane is None or e.plane == plane)
                and e.end_ns > self.lo and e.start_ns < self.hi]

    def busy(self, plane: str) -> List[List[float]]:
        return clip(merge((e.start_ns, e.end_ns) for e in self.ops(plane)),
                    self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the device planes."""
        if not self.planes:
            return 0.0
        return sum(total(self.busy(p)) for p in self.planes) * 1e-9 / len(
            self.planes)

    def op_seconds(self, pattern: Optional[re.Pattern] = None
                   ) -> Dict[str, float]:
        """Device seconds per op name (summed over planes), optionally
        only the names that match ``pattern``."""
        out: Dict[str, float] = {}
        for e in self.ops():
            if pattern is None or pattern.search(e.name):
                d = min(e.end_ns, self.hi) - max(e.start_ns, self.lo)
                out[e.name] = out.get(e.name, 0.0) + d * 1e-9
        return out

    def span_seconds(self, name: str) -> float:
        return sum(e.dur_ns for e in self.events if e.name == name) * 1e-9

    def exposed_collective_s(self) -> float:
        """Seconds per plane, averaged, in which a collective runs and no
        other op does."""
        if not self.planes:
            return 0.0
        acc = 0.0
        for p in self.planes:
            ops = self.ops(p)
            coll = merge((e.start_ns, e.end_ns) for e in ops
                         if COLLECTIVE.search(e.name))
            comp = merge((e.start_ns, e.end_ns) for e in ops
                         if not COLLECTIVE.search(e.name))
            acc += total(clip(subtract(coll, comp), self.lo, self.hi))
        return acc * 1e-9 / len(self.planes)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Device idle time in the window, by the host span that covers
        the middle of each gap, summed per span name, longest first."""
        spans = [e for e in self.events
                 if e.name.startswith(HOST_SPAN_PREFIX)
                 and e.name != "bench.round"]
        acc: Dict[str, float] = {}
        for p in self.planes:
            gaps = subtract([[self.lo, self.hi]], self.busy(p))
            for s, e in gaps:
                mid = 0.5 * (s + e)
                where = [sp for sp in spans
                         if sp.start_ns <= mid < sp.end_ns]
                label = (min(where, key=lambda sp: sp.dur_ns).name
                         if where else "host outside bench spans")
                acc[label] = acc.get(label, 0.0) + (e - s) * 1e-9 / len(
                    self.planes)
        return sorted(acc.items(), key=lambda kv: -kv[1])
