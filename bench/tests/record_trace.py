#!/usr/bin/env python3
"""Records the small chip trace the trace reducer's test reads
(``bench/tests/data/dashboard.xplane.pb``).

    python3 bench/tests/record_trace.py <out-dir>

It serves one cycle of the PubMed dashboard traffic (five batches of 8, one
per shape) at 20,000 documents through the benchmark's own loop, with the
profiler on and the same options as a traced run, copies the ``.xplane.pb``
to ``<out-dir>`` and prints the trace's planes and lines."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402

from bench import run as R  # noqa: E402
from bench import spec as S  # noqa: E402
from collections import deque  # noqa: E402

from bench.loop import Batcher, Record, Request  # noqa: E402
from bench.traffic import ClosedClients  # noqa: E402


def main() -> int:
    out = Path(sys.argv[1])
    cell = S.cell(ROOT, "pubmed-m-1m.dashboard")
    pub, n = cell.config["published"], 20000
    cell.config.update(
        n_docs=n, n_authors=max(1, round(n * pub["n_authors"] / pub["n_docs"])),
        dt_rows=int(n * pub["dt_rows"] / pub["n_docs"]),
        da_rows=int(n * pub["da_rows"] / pub["n_docs"]))
    R.find_devices(1)
    R.enable_compile_cache()
    from bench.sut import System

    _, system, sampler, _ = R.build(cell, 1, System)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    batcher = Batcher(system.execute, 8, lambda r: False)
    clients = ClosedClients(cell.traffic, sampler, 1)
    queue = deque(Request(c, *vars(clients.next(c)).values(), due=0.0)
                  for c in range(clients.n))
    rec = Record()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(len(cell.traffic["cycle"])):
            batcher.step(queue, rec)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "dashboard.xplane.pb")
    print(f"batches in the window: {len(rec.batches)}; "
          f"{os.path.getsize(src)} bytes")
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(src).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(plane.name, lines)
        for ln in plane.lines:
            names = sorted({ev.name for ev in ln.events})
            print("   ", ln.name, names[:25])
    return 0


if __name__ == "__main__":
    sys.exit(main())
