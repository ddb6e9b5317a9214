#!/usr/bin/env python3
"""Chip benchmark of the relationship-query engine, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run finds the TPU (and fails, printing no result, without one or on a
device kind missing from ``bench/peaks.json``), builds the cell's deployment
from the seed, prepares and warms the cell's query shapes at its one batch
bucket, serves its traffic for ``--seconds`` through the program's
``repro.robust.run_batch_with_policy``, then checks the window's answers
against the plain reference (``bench/check.py``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones, read from a profiler trace of the window), ``device``,
``breakdown`` (traced runs) and ``check``, the numbers compared with their
limits, which also end standard error.

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json``. The compile cache lives in ``$JAX_COMPILATION_CACHE_DIR``
or else ``.jax_cache/`` at the checkout's root; a traced run writes its
trace under ``.bench_out/`` there and deletes it once read."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)  # import bench.* as a package, never its files as top-level modules
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import spec as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.check import compare_requests, judge  # noqa: E402
from bench.data import generate  # noqa: E402
from bench.graph import Graph  # noqa: E402
from bench.loop import DRAIN_S, Batcher, run_closed, run_open  # noqa: E402
from bench.traffic import (  # noqa: E402
    ClosedClients, Sampler, open_arrivals, shapes_of, sql_params, warmup_draws,
)

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoDevice(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_devices(chips: int):
    """The chips this cell runs on, and the peaks of their kind."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found platform {d0.platform!r} ({d0.device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = S.load_json(PEAKS)["devices"]
    if d0.device_kind not in peaks:
        raise NoDevice(f"device kind {d0.device_kind!r} is not in {PEAKS.name}")
    return devs[:chips], peaks[d0.device_kind]


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Events:
    """Counts compile-cache hits and misses and backend compiles."""

    NAMES = ("/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses")
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counts = {"cache_hits": 0, "cache_misses": 0, "compiles": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event in self.NAMES:
            self.counts[event.rsplit("/", 1)[1]] += 1

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE:
            self.counts["compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


class RunView:
    """What the metric readers read (``bench/metrics``)."""

    def __init__(self, record, setup_s, trace, graph, schema, peaks):
        self.record = record
        self.setup_s = setup_s
        self.trace = trace
        self.graph = graph
        self.schema = schema
        self.peaks = peaks
        self.gave_up_at = record.t_close + DRAIN_S
        self.trace_window = None
        if trace is not None:
            lo, _ = trace.window()
            self.trace_window = (lo, lo + (record.t_end - record.t0) * 1e9)
        self._bytes = None

    def traced_requests(self) -> int:
        return sum(r.status != "error" for r in self.record.in_window())

    def useful_bytes(self) -> float:
        """Useful bytes (``bench/bytes.py``) of the requests answered in the
        traced window."""
        if self._bytes is None:
            from bench.bytes import useful_bytes

            by_shape: dict[str, list] = {}
            for r in self.record.in_window():
                if r.status != "error":
                    by_shape.setdefault(r.shape, []).append(r.params)
            total = 0.0
            for shape, ps in by_shape.items():
                for i in range(0, len(ps), 64):
                    part = ps[i:i + 64]
                    arrays = {k: np.asarray([p[k] for p in part]) for k in part[0]}
                    total += float(useful_bytes(self.graph, self.schema, shape,
                                                arrays).sum())
            self._bytes = total
        return self._bytes


def new_sampler(cell: S.Cell, data, seed: int):
    """The cell's parameter sampler, and one bucket of draws per shape for
    the warm calls, which come first in its turns."""
    traffic = cell.traffic
    queries = {s: cell.config["queries"][s]["sql"] for s in shapes_of(traffic)}
    sampler = Sampler(data, traffic["params"],
                      {s: sql_params(q) for s, q in queries.items()}, seed)
    rng = np.random.default_rng([seed, 5])
    warm = {s: [sampler.draw(rng, s) for _ in range(traffic["bucket"])] for s in queries}
    return sampler, warm


def build(cell: S.Cell, seed: int, system_cls):
    """Data, database, prepared shapes and one warm call per shape."""
    cfg, traffic = cell.config, cell.traffic
    phases = {}
    t = time.perf_counter()
    data = generate(cfg["schema"], cfg, seed)
    phases["data_s"] = time.perf_counter() - t
    names = shapes_of(traffic)
    queries = {s: cfg["queries"][s]["sql"] for s in names}
    t = time.perf_counter()
    system = system_cls(data, queries)
    phases["build_and_prepare_s"] = time.perf_counter() - t
    sampler, warm = new_sampler(cell, data, seed)
    t = time.perf_counter()
    for s, draws in warm.items():
        outs = system.execute(s, {k: np.asarray([d.params[k] for d in draws])
                                  for k in draws[0].params})
        bad = [o.status for o in outs if o.status != "ok"]
        if bad:
            raise RuntimeError(f"warm-up of {s} ended {bad[0]}")
    phases["warm_s"] = time.perf_counter() - t
    return data, system, sampler, phases


def serve(cell: S.Cell, system, sampler, seed: int, seconds: float):
    traffic = cell.traffic
    frac = traffic.get("check_fraction", 1.0)

    def keep(r) -> bool:
        if r.id < 0:
            return False
        return frac >= 1.0 or np.random.default_rng([seed, 4, r.id]).random() < frac

    batcher = Batcher(system.execute, traffic["bucket"], keep)
    if traffic["loop"] == "closed":
        return run_closed(batcher, ClosedClients(traffic, sampler, seed), seconds,
                          warmup_batches=len(traffic["cycle"]))
    return run_open(batcher, open_arrivals(traffic, sampler, seed, seconds),
                    warmup_draws(traffic, sampler, seed), seconds)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(cell: S.Cell, seed: int, seconds: float, traced: bool, devices, peaks,
             system_cls=None) -> dict:
    import jax

    if system_cls is None:
        from bench.sut import System as system_cls
    events = Events()
    t_build = time.perf_counter()
    data, system, sampler, phases = build(cell, seed, system_cls)
    phases = {"start_s": t_build - T_START, **phases}
    t_built = time.perf_counter()
    trace_dir = None
    if traced:
        out = ROOT / ".bench_out"
        out.mkdir(exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix="trace-", dir=out)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = events.snapshot()
    rec = serve(cell, system, sampler, seed, seconds)
    phases["setup_events"] = before
    window_events = {k: v - before[k] for k, v in events.snapshot().items()}
    setup_s = rec.t0 - T_START
    if traced:
        jax.profiler.stop_trace()
    mem = memory_peak(devices)
    phases.update(loop_warmup_s=rec.t0 - t_built, setup_s=setup_s)
    log("phases: " + json.dumps(phases))
    log("window events: " + json.dumps(window_events))
    log("window batches (shape, rows, ms): " + json.dumps(
        [[b.shape, b.rows, round((b.end - b.start) * 1e3, 1)]
         for b in rec.batches if b.start < rec.t_close]))
    del system
    gc.collect()

    trace = None
    if traced:
        t = time.perf_counter()
        trace = T.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: read in {time.perf_counter() - t:.1f}s")

    cfg = cell.config
    window = rec.in_window() if cell.traffic["loop"] == "closed" else rec.requests
    failed = sum(r.status in ("", "error") for r in window)
    t = time.perf_counter()
    graph = Graph(data)
    kept = [r for r in window if r.value is not None]
    numbers = compare_requests(graph, cfg["schema"], cfg["queries"], kept)
    numbers["failed"] = failed
    correct, check = judge(numbers, cfg["limits"])
    log(f"check: {len(kept)} of {len(window)} answers compared in "
        f"{time.perf_counter() - t:.1f}s")

    view = RunView(rec, setup_s, trace, graph, cfg["schema"], peaks)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.reader.read(view)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(window), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        lo, hi = view.trace_window
        device["busy_s"] = T.busy_ns(trace, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": T.top_ops(trace, lo, hi),
                               "idle_gaps": T.idle_gaps(trace, lo, hi)}
    result["check"] = check
    for name, c in check.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number ≥ 0")
    cell = S.cell(ROOT, args.workload)
    try:
        import repro  # noqa: F401 — the system under test, from src/
    except ImportError:
        log(f"FAIL: the program (src/repro) is not in {ROOT}")
        return 1
    try:
        devices, peaks = find_devices(cell.chips)
    except NoDevice as e:
        log(f"FAIL: {e}")
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
