#!/usr/bin/env python3
"""Readings of the control, the bfloat16 reference put in the program's
place, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --requests 120

For each seed it builds the cell's data and the requests its traffic sends
first (a closed loop's clients in turn, an open loop's first arrivals), as
many as ``--requests``, computes the float64 reference and the control's
answers for them, and prints the numbers ``bench/check.py`` compares with
the cell's limits. The control must come out not correct. It needs no chip,
and the benchmark's own runs never run it."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from bench import spec as S  # noqa: E402
from bench.check import compare_requests, judge, reference_answers  # noqa: E402
from bench.data import generate  # noqa: E402
from bench.graph import Graph  # noqa: E402
from bench.loop import Request  # noqa: E402
from bench.reference import Bfloat16  # noqa: E402
from bench.traffic import (  # noqa: E402
    ClosedClients, Sampler, open_arrivals, shapes_of, sql_params,
)


def first_requests(cell: S.Cell, data, seed: int, n: int) -> list[Request]:
    cfg, traffic = cell.config, cell.traffic
    queries = {s: cfg["queries"][s]["sql"] for s in shapes_of(traffic)}
    sampler = Sampler(data, traffic["params"],
                      {s: sql_params(q) for s, q in queries.items()}, seed)
    if traffic["loop"] == "closed":
        clients = ClosedClients(traffic, sampler, seed)
        draws = []
        while len(draws) < n:
            draws += [clients.next(c) for c in range(clients.n)]
    else:
        seconds = n / traffic["rate_qps"] + 1.0
        draws = [d for _, d in open_arrivals(traffic, sampler, seed, seconds)]
    return [Request(i, d.shape, d.params, 0.0) for i, d in enumerate(draws[:n])]


def readings(cell: S.Cell, seed: int, n: int) -> dict:
    cfg = cell.config
    data = generate(cfg["schema"], cfg, seed)
    graph = Graph(data)
    reqs = first_requests(cell, data, seed, n)
    control = Bfloat16(graph)
    numbers = compare_requests(
        graph, cfg["schema"], cfg["queries"], reqs,
        answers=lambda shape, ps: reference_answers(graph, cfg["schema"], shape, ps,
                                                    ops=control))
    numbers["failed"] = 0
    correct, check = judge(numbers, cfg["limits"])
    return {"seed": seed, "requests": len(reqs), "control_correct": correct,
            "check": check}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args()
    cell = S.cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(cell, seed, args.requests)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
