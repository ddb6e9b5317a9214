#!/usr/bin/env python3
"""What the program puts into a profiler trace, reduced to a breakdown.

    python3 bench/program_trace.py <trace.xplane.pb[.gz] | dir> [--config <file>] [--top <n>]
    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s> [--top <n>]

The first form reads a trace and prints, as the last line of standard
output, one JSON object; the second runs one traced window of a cell exactly
as ``bench/run.py --trace 1`` does, result line included, and prints the
same object on standard error (``program breakdown: {...}``), reduced from
the trace before the run deletes it. The object holds:

- ``hops``: the ten (``--top``) (shape / hop label) pairs with the most hop-kernel device
  self time in the window, as ``[name, device s, rows, gathers per row]``.
  A kernel's label is its ``kernel_metadata`` (``{"hop": ...}``, the
  executor's ``h<k>:<table>.<key>-><entity>:<layout>``); its shape is the
  query of the runner's ``repro.batch`` span that holds it (the query id,
  or the configuration's shape name with ``--config`` or ``--workload``);
  rows and gathers come from that batch's ``repro.hops`` stats.
  ``hops_attributed`` is the share of hop-kernel device time so named.
- ``clock``: each device module run (the ``XLA Modules`` line) linked to the
  ``repro.dispatch`` that launched it and the ``repro.device_wait`` that
  waited for it. Causality (dispatch start ≤ module start, module end ≤ wait
  end) bounds the offset of the device's clock from the host's for each
  run; ``median_ms`` and ``worst_ms`` are the smallest offsets each run
  needs, ``offset_ms`` one offset that suits them all (the nearest to 0),
  ``linked`` of ``modules``.
- ``idle_gaps_program``: the ten longest idle gaps of the device in the
  window, moved onto the host's clock by that offset and named by the
  innermost ``repro.*`` or ``bench.*`` span open at their midpoint.
- ``host_ms_per_batch``: for each ``repro.*`` span, its time per batch of
  the window (the batches whose ``repro.batch`` started in it), in ms — the
  in-program split of the engine's host path.
- ``compiles_in_window``: ``repro.compile`` spans that started in the window.

Times are in the trace's nanoseconds until reported."""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import statistics
import sys
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import trace as T  # noqa: E402

MODULES_LINE = "XLA Modules"
KERNEL = re.compile(r"gqfast_(hop|fused_hops)(\.\d+)?$")
# kernel_metadata={"hop":"<label>"}, the JSON possibly escaped or split over lines
LABEL = re.compile(r'kernel_metadata=\{.*?\\?"hop\\?"\s*:\s*\\?"([^"\\]+)', re.S)
HOST_PREFIXES = ("repro.", "bench.")
#: How far a module run may sit outside its dispatch-to-wait span before it
#: is not linked (the clocks' skew: up to 1.2 ms seen on a v5e host).
SKEW_NS = 5e6


@dataclass
class ProgramTrace:
    # (name, start, end, stats) of every repro.* and bench.* host event
    events: list = field(default_factory=list)
    # (module name, start, end) of every module run of the first device
    modules: list = field(default_factory=list)
    # (op name, label, start, end, self time) of every hop kernel event
    kernels: list = field(default_factory=list)
    busy: list = field(default_factory=list)  # merged op intervals, first device

    def named(self, name: str) -> list:
        return [e for e in self.events if e[0] == name]

    def window(self) -> tuple[float, float]:
        for name, s, e, _ in self.events:
            if name == "bench.window":
                return s, e
        raise ValueError("the trace holds no bench.window span")


def label_of(event_name: str) -> str:
    m = LABEL.search(event_name)
    return m.group(1) if m else ""


def from_profile(pd) -> ProgramTrace:
    pt = ProgramTrace()
    device_seen = False
    for plane in pd.planes:
        if T.DEVICE_PLANE.match(plane.name) and not device_seen:
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    pt.modules += [(ev.name, float(ev.start_ns),
                                    float(ev.start_ns) + float(ev.duration_ns))
                                   for ev in line.events]
                elif line.name == T.OPS_LINE:
                    ops += sorted((float(ev.start_ns),
                                   float(ev.start_ns) + float(ev.duration_ns), ev.name)
                                  for ev in line.events)
            if ops:
                device_seen = True
                selfs = T.self_times([(s, e) for s, e, _ in ops])
                for (s, e, name), t in zip(ops, selfs):
                    op = T.op_name(name)
                    if KERNEL.match(op):
                        pt.kernels.append((op, label_of(name), s, e, t))
                pt.busy = T.merge([(s, e) for s, e, _ in ops])
        elif plane.name == T.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        s = float(ev.start_ns)
                        pt.events.append((ev.name, s, s + float(ev.duration_ns),
                                          dict(ev.stats)))
    pt.events.sort(key=lambda x: (x[1], -x[2]))
    pt.modules.sort(key=lambda x: x[1])
    return pt


def load(path: str) -> ProgramTrace:
    """A trace file (``.xplane.pb`` or ``.xplane.pb.gz``), or the newest
    ``*.xplane.pb`` under a directory."""
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


def link_modules(pt: ProgramTrace) -> list[tuple]:
    """``(module, dispatch, device_wait)`` for each module run: the
    ``repro.dispatch`` whose start is nearest the run's (the device clock
    may read ahead of or behind the host's) and the first
    ``repro.device_wait`` after it; None where the run does not lie inside
    that dispatch-to-wait span, give or take the clocks' skew."""
    dispatches = pt.named("repro.dispatch")
    waits = pt.named("repro.device_wait")
    d_starts = [d[1] for d in dispatches]
    w_starts = [w[1] for w in waits]
    out = []
    for mod in pt.modules:
        i = bisect.bisect_left(d_starts, mod[1])
        near = [k for k in (i - 1, i) if 0 <= k < len(dispatches)]
        disp = wait = None
        if near:
            k = min(near, key=lambda k: abs(d_starts[k] - mod[1]))
            j = bisect.bisect_left(w_starts, dispatches[k][2])
            if (j < len(waits) and abs(d_starts[k] - mod[1]) <= SKEW_NS
                    and waits[j][2] >= mod[2] - SKEW_NS):
                disp, wait = dispatches[k], waits[j]
        out.append((mod, disp, wait))
    return out


def clock(pt: ProgramTrace, lo: float = float("-inf"),
          hi: float = float("inf")) -> dict:
    """The offset of the device clock from the host's that causality
    implies, over the module runs that overlap ``[lo, hi]`` (a run's start
    may read before the window's on the device's clock)."""
    runs = [x for x in link_modules(pt) if x[0][2] >= lo and x[0][1] <= hi]
    bounds = [(m[2] - w[2], m[1] - d[1]) for m, d, w in runs if d is not None]
    need = [0.0 if a <= 0.0 <= b else (a if a > 0 else b) for a, b in bounds]
    res = {"modules": len(runs), "linked": len(bounds)}
    if not bounds:
        return {**res, "median_ms": None, "worst_ms": None, "offset_ms": None}
    lo_all, hi_all = max(a for a, _ in bounds), min(b for _, b in bounds)
    offset = min(max(0.0, lo_all), hi_all) if lo_all <= hi_all else (lo_all + hi_all) / 2
    return {**res, "median_ms": statistics.median(abs(x) for x in need) / 1e6,
            "worst_ms": max(abs(x) for x in need) / 1e6, "offset_ms": offset / 1e6,
            "consistent": lo_all <= hi_all}


def _innermost(events, t: float):
    """The shortest of ``events`` open at ``t`` (``bench.window`` aside)."""
    best = None
    for ev in events:
        if ev[1] <= t <= ev[2] and ev[0] != "bench.window":
            if best is None or ev[2] - ev[1] < best[2] - best[1]:
                best = ev
    return best


def _hop_stats(stats: dict) -> dict:
    """``h<k>`` stats of a ``repro.hops`` event → {label: (rows, gathers)}."""
    out = {}
    for key, val in stats.items():
        if not re.fullmatch(r"h\d+", key):
            continue
        label, *kv = str(val).split(" ")
        kv = dict(x.split("=", 1) for x in kv)
        gathers = None if kv["gathers"] == "-" else int(kv["gathers"])
        out[f"{key}:{label}"] = (int(kv["rows"]), gathers)
    return out


def hops(pt: ProgramTrace, lo: float, hi: float, shapes: dict | None = None,
         offset_ns: float = 0.0, n: int = 10):
    """(top ``n`` [name, device s, rows, gathers/row], share of hop-kernel
    time attributed to a shape and a hop)."""
    shapes = shapes or {}
    batches = pt.named("repro.batch")
    hop_events = pt.named("repro.hops")
    acc: dict[str, list] = {}
    total = named = 0.0
    counted: set = set()
    for op, label, s, e, t in pt.kernels:
        d = T._clipped(s, e, t, lo + offset_ns, hi + offset_ns)
        if d <= 0:
            continue
        total += d
        batch = _innermost(batches, (s + e) / 2 - offset_ns)
        if not label or batch is None:
            continue
        named += d
        shape = shapes.get(batch[3].get("query"), batch[3].get("query", "?"))
        key = f"{shape}/{label}"
        row = acc.setdefault(key, [0.0, 0, 0, 0])
        row[0] += d
        if (key, batch[1]) in counted:
            continue  # one batch's counters once per (shape, hop)
        counted.add((key, batch[1]))
        stats = {}
        for h in hop_events:
            if batch[1] <= h[1] <= batch[2]:
                stats.update(_hop_stats(h[3]))
        for part in label.split("+"):
            rows, gathers = stats.get(part, (0, None))
            row[1] += rows
            if gathers is not None:
                row[2] += gathers
                row[3] += rows
    top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
    out = [[k, v[0] / 1e9, v[1], v[2] / v[3] if v[3] else None] for k, v in top]
    return out, (named / total if total else None)


def idle_gaps_program(pt: ProgramTrace, lo: float, hi: float,
                      offset_ns: float = 0.0, n: int = 10):
    """The ``n`` longest device idle gaps in ``[lo, hi]`` (host clock), each
    named by the innermost program or benchmark span open at its midpoint."""
    busy = [(s - offset_ns, e - offset_ns) for s, e in pt.busy]
    g = sorted(T.gaps(busy, lo, hi), key=lambda x: x[0] - x[1])[:n]
    out = []
    for s, e in g:
        ev = _innermost(pt.events, (s + e) / 2)
        out.append((ev[0] if ev else "idle", (e - s) / 1e9))
    return out


def host_ms_per_batch(pt: ProgramTrace, lo: float, hi: float) -> dict:
    """Each ``repro.*`` span's time per batch of ``[lo, hi]``, in ms."""
    batches = [b for b in pt.named("repro.batch") if lo <= b[1] <= hi]
    acc: dict[str, float] = {}
    for name, s, e, _ in pt.events:
        if name.startswith("repro.") and any(b[1] <= s <= b[2] for b in batches):
            key = name[len("repro."):]
            acc[key] = acc.get(key, 0.0) + (e - s)
    return {k: v / len(batches) / 1e6 for k, v in acc.items()} if batches else {}


def breakdown(pt: ProgramTrace, shapes: dict | None = None,
              window: tuple[float, float] | None = None, top: int = 10) -> dict:
    lo, hi = window if window is not None else pt.window()
    clk = clock(pt, lo, hi)
    off = (clk["offset_ms"] or 0.0) * 1e6
    named, share = hops(pt, lo, hi, shapes, off, n=top)
    compiles = [e for e in pt.named("repro.compile") if lo <= e[1] <= hi]
    return {"hops": named, "hops_attributed": share, "clock": clk,
            "idle_gaps_program": idle_gaps_program(pt, lo, hi, off),
            "host_ms_per_batch": host_ms_per_batch(pt, lo, hi),
            "compiles_in_window": len(compiles)}


def shape_names(config: dict) -> dict:
    """query id (the runner's ``repro.batch`` stat) → the configuration's
    shape name."""
    from repro.core.engine import query_id

    return {query_id(q["sql"]): s for s, q in config["queries"].items()}


def _run_cell(argv, top: int) -> int:
    """``bench/run.py`` with its trace reduced here before it is deleted."""
    from bench import run as R
    from bench import spec as S

    args = dict(zip(argv[::2], argv[1::2]))
    shapes = shape_names(S.cell(ROOT, args["--workload"]).config)
    load_trace = T.load

    def load_and_reduce(path):
        tr = load_trace(path)
        out = breakdown(load(path), shapes, top=top)
        print("program breakdown: " + json.dumps(out),
              file=sys.stderr, flush=True)
        return tr

    T.load = load_and_reduce
    return R.main(list(argv) + ["--trace", "1"])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    top = 10
    if "--top" in argv:
        i = argv.index("--top")
        top = int(argv[i + 1])
        del argv[i:i + 2]
    if "--workload" in argv:
        return _run_cell(argv, top)
    shapes = None
    if "--config" in argv:
        i = argv.index("--config")
        with open(argv[i + 1]) as f:
            shapes = shape_names(json.load(f))
        del argv[i:i + 2]
    print(json.dumps(breakdown(load(argv[0]), shapes, top=top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
