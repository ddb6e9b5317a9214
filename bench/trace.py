"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the per-layer metrics read: each
device's busy intervals (the union of its ``XLA Ops`` events), the device
time of each op, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``\\ s), all on the trace's one clock, in nanoseconds.

On a TPU an ``XLA Ops`` event is named by its HLO text
(``%gqfast_hop.2 = f32[8,1000064]{...} custom-call(...)``); an op is known
by the instruction name before `` = `` without its ``%``, and its self time
leaves out the ops nested inside it (a ``conditional`` holds the kernel its
branch calls)."""
from __future__ import annotations

import bisect
import glob
import gzip
import itertools
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


class Busy:
    """Merged busy intervals of one device, with the time they cover up to
    any instant in O(log n)."""

    def __init__(self, intervals):
        self.intervals = merge(intervals)
        self.starts = [s for s, _ in self.intervals]
        self.cum = [0.0] + list(itertools.accumulate(e - s for s, e in self.intervals))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.intervals[i - 1]
        return self.cum[i - 1] + min(e, t) - s

    def covered(self, lo: float, hi: float) -> float:
        return max(0.0, self._upto(hi) - self._upto(lo))


def op_name(event_name: str) -> str:
    """``%gqfast_hop.2 = f32[...] custom-call(...)`` -> ``gqfast_hop.2``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(events) -> list[float]:
    """Each event's duration less the events nested inside it, for events
    of one line given as (start, end) sorted by start."""
    out = [e - s for s, e in events]
    stack: list[int] = []
    for i, (s, e) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(e, events[stack[-1]][1]) - s
        stack.append(i)
    return out


@dataclass
class Trace:
    busy: list[Busy] = field(default_factory=list)  # per device that ran ops
    # (name, start, end, self time) of every device op
    ops: list[tuple[str, float, float, float]] = field(default_factory=list)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> tuple[float, float]:
        """The ``bench.window`` span."""
        for name, s, e in self.spans:
            if name == "bench.window":
                return s, e
        raise ValueError("the trace holds no bench.window span")


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            intervals = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = sorted((float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns),
                              op_name(ev.name)) for ev in line.events)
                iv = [(s, e) for s, e, _ in evs]
                tr.ops += [(n, s, e, t) for (s, e, n), t in zip(evs, self_times(iv))]
                intervals += iv
            if intervals:
                tr.busy.append(Busy(intervals))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        tr.spans.append((ev.name, s, s + float(ev.duration_ns)))
    tr.spans.sort(key=lambda x: x[1])
    return tr


def load(path: str) -> Trace:
    """A trace file (``.xplane.pb``, or gzipped ``.xplane.pb.gz``), or the
    newest ``*.xplane.pb`` under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()))
    return from_profile(ProfileData.from_file(path))


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Busy time in ``[lo, hi]``, averaged over the devices that ran ops."""
    if not tr.busy:
        return 0.0
    return sum(b.covered(lo, hi) for b in tr.busy) / len(tr.busy)


def _clipped(s: float, e: float, t: float, lo: float, hi: float) -> float:
    """Self time ``t`` of an op over ``[s, e]``, scaled to its part in ``[lo, hi]``."""
    inside = max(0.0, min(e, hi) - max(s, lo))
    return t * inside / (e - s) if e > s else 0.0


def op_ns(tr: Trace, pattern: re.Pattern, lo: float, hi: float) -> float:
    """Device self time of the ops whose name matches, in ``[lo, hi]``."""
    return sum(_clipped(s, e, t, lo, hi)
               for name, s, e, t in tr.ops if pattern.match(name))


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` op names with the most device self time, in seconds."""
    acc: dict[str, float] = {}
    for name, s, e, t in tr.ops:
        d = _clipped(s, e, t, lo, hi)
        if d > 0:
            acc[name] = acc.get(name, 0.0) + d
    return [(k, v / 1e9) for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def span_at(tr: Trace, t: float) -> str:
    """The innermost benchmark span open at ``t`` (``idle`` outside all)."""
    best, best_len = "idle", float("inf")
    for name, s, e in tr.spans:
        if s <= t <= e and name != "bench.window" and e - s < best_len:
            best, best_len = name, e - s
    return best


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` longest idle gaps of the first device, each named by the
    benchmark span open at its midpoint, in seconds."""
    if not tr.busy:
        return []
    g = sorted(gaps(tr.busy[0].intervals, lo, hi), key=lambda x: x[0] - x[1])[:n]
    return [(span_at(tr, (s + e) / 2), (e - s) / 1e9) for s, e in g]
