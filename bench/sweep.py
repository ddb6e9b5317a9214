#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find its knee once, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 40,80,120

One process builds the cell's deployment and warms it as a run does, then
offers each rate in turn for ``--seconds``, with the parameter draws a run's
window takes, and prints, per rate, the rate completed, p50/p95 latency, how
long the queue took to drain after the window closed, and each shape's batch
walls (p50, p95, max, in ms). The knee is the highest rate whose drain stays
within about a batch and whose completed rate keeps up with the offered one;
a cell runs at a fixed share of it, written into its traffic file."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import run as R  # noqa: E402
from bench import spec as S  # noqa: E402
from bench.loop import Batcher, run_open  # noqa: E402
from bench.stats import percentile  # noqa: E402
from bench.traffic import open_arrivals, warmup_draws  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    args = ap.parse_args()
    cell = S.cell(ROOT, args.workload)
    if cell.traffic["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    try:
        R.find_devices(cell.chips)
    except R.NoDevice as e:
        R.log(f"FAIL: {e}")
        return 1
    R.enable_compile_cache()
    from bench.sut import System

    data, system, _, phases = R.build(cell, args.seed, System)
    R.log("phases: " + json.dumps(phases))
    batcher = Batcher(system.execute, cell.traffic["bucket"], lambda r: False)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_qps=rate)
        sampler, _ = R.new_sampler(cell, data, args.seed)  # the turns a run's window takes
        rec = run_open(batcher, open_arrivals(traffic, sampler, args.seed + i, args.seconds),
                       warmup_draws(traffic, sampler, args.seed), args.seconds)
        done = [r for r in rec.requests if r.status and r.status != "error"]
        lat = [(r.done - r.due) * 1e3 for r in done]
        last = max(r.done for r in done)
        walls = {}
        for b in rec.batches:
            walls.setdefault(b.shape, []).append((b.end - b.start) * 1e3)
        every = [w for ws in walls.values() for w in ws]
        print(json.dumps({
            "rate_offered": rate, "offered": len(rec.requests), "answered": len(done),
            "rate_completed": len(done) / (last - rec.t0),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "drain_s": last - rec.t_close, "batches": len(every),
            "rows_per_batch": len(done) / max(len(every), 1),
            "batch_ms_p50": percentile(every, 50),
            "batch_ms": {s: [percentile(ws, 50), percentile(ws, 95), max(ws)]
                         for s, ws in walls.items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
