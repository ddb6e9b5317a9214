#!/usr/bin/env python3
"""Smoke test of the served relationship-query path on a TPU.

    python3 chip_smoke.py               # one chip: the Pallas serving path
    python3 chip_smoke.py --chips 4     # four chips: the edge-sharded path

One process. It builds a PubMed deployment at the ratios of the paper's
Table 1 (default 1,000,000 documents: 27,883 MeSH terms, ~270k authors,
~38.6M DT rows, ~2.6M DA rows) on the device, prepares the five dashboard
queries (AS, SD, FSD, AD, FAD) with the engine's defaults, serves a few
seeded batches of 8 plus one single query per shape through the
fault-tolerant runner, and checks every answer against the numpy reference
(``core/reference.py``) at rtol 1e-4 / atol 1e-5. It fails on a prepare
error, any outcome that is not ``ok`` on rung ``active``, any nonzero
``robust.demotions.*`` counter, any mismatch, or a shape whose plan routes a
hop to a Pallas kernel while its compiled executable holds no
``tpu_custom_call``. With ``--chips 4`` it runs only the edge-sharded engine
on a 4-device mesh for the same queries, checks that each device holds its
own quarter of every edge array, and compares with the same reference.

It exits non-zero, with no result line, when JAX finds no TPU or when the
``repro`` package is not next to this file. On success the last line of
standard output is ``{"ok": true, "device": {...}}`` as JAX reports the
device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SHAPES = ("AS", "SD", "FSD", "AD", "FAD")
RTOL, ATOL = 1e-4, 1e-5


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def params_for(shape: str, rng, schema, n: int) -> dict:
    """Seeded parameter bindings drawn from the loaded id domains."""
    dom = {e: schema.entities[e].size for e in ("Author", "Document", "Term")}
    if shape == "AS":
        return {"a0": rng.integers(0, dom["Author"], n)}
    if shape in ("SD", "FSD"):
        return {"d0": rng.integers(0, dom["Document"], n)}
    return {"t1": rng.integers(0, dom["Term"], n),
            "t2": rng.integers(0, dom["Term"], n)}


class Checker:
    """Compares answers with the numpy reference and keeps the worst error
    (in units of the tolerance: ≤ 1 passes)."""

    def __init__(self, schema):
        from repro.core.planner import plan_query
        from repro.core.reference import NumpyQueryEngine
        from repro.core.sql import parse
        from repro.data import synth_graph as SG

        t0 = time.perf_counter()
        self.oracle = NumpyQueryEngine(schema, collapse=True)
        self.plans = {s: plan_query(schema, parse(SG.PUBMED_QUERIES[s]))
                      for s in SHAPES}
        self.worst = 0.0
        self.checked = 0
        log(f"reference: numpy engine built in {time.perf_counter() - t0:.1f}s")

    def check(self, shape: str, params: dict, got) -> None:
        import numpy as np

        want = self.oracle.execute_plan(self.plans[shape], params)
        got = np.asarray(got, np.float64)
        if got.shape != want.shape:
            raise SmokeFailure(f"{shape} {params}: shape {got.shape} != {want.shape}")
        err = float(np.max(np.abs(got - want) / (ATOL + RTOL * np.abs(want)),
                           initial=0.0))
        self.worst = max(self.worst, err)
        self.checked += 1
        if err > 1.0:
            raise SmokeFailure(
                f"{shape} {params}: answer differs from the reference "
                f"(max |Δ| {float(np.abs(got - want).max()):.3g}, "
                f"{err:.3g}× the tolerance)"
            )


def build_deployment(n_docs: int, seed: int):
    import jax

    from repro.core.engine import GQFastDatabase
    from repro.data import synth_graph as SG

    full = SG.PUBMED_TABLE1
    t0 = time.perf_counter()
    schema = SG.pubmed_table1_scale(n_docs, seed=seed)
    log(f"data: generated in {time.perf_counter() - t0:.1f}s (seed {seed})")
    sizes = {
        "documents": schema.entities["Document"].size,
        "terms": schema.entities["Term"].size,
        "authors": schema.entities["Author"].size,
        "DT rows": schema.relationships["DT"].num_rows,
        "DA rows": schema.relationships["DA"].num_rows,
    }
    log("deployment: PubMed at Table 1 ratios: "
        + ", ".join(f"{k} {v:,}" for k, v in sizes.items()))
    log(f"cut: documents {n_docs:,} of {full['n_docs']:,} "
        f"(×{n_docs / full['n_docs']:.4f}); authors, DT and DA rows scale "
        "with them; the 27,883-term vocabulary is kept whole")
    t0 = time.perf_counter()
    db = GQFastDatabase(schema, account_space=False)
    jax.block_until_ready([di.src_ids for di in db.device.indexes.values()])
    log(f"database: built and placed on the device in "
        f"{time.perf_counter() - t0:.1f}s")
    dev = db.space_report()["device"]
    log("device bytes: " + json.dumps(
        {k: v for k, v in dev.items() if not isinstance(v, (dict, list))}))
    return schema, db


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return "memory_stats: " + json.dumps({k: stats[k] for k in keep if k in stats})


def count_kernels(fn, args) -> int:
    return fn.lower(*args).compile().as_text().count("tpu_custom_call")


def run_one_chip(args, device) -> None:
    import numpy as np

    from repro.core.engine import GQFastEngine
    from repro.data import synth_graph as SG
    from repro.obs.metrics import MetricsRegistry
    from repro.robust import RobustPolicy, run_batch_with_policy, run_with_policy
    from repro.robust.errors import QueryError

    schema, db = build_deployment(args.docs, args.seed)
    log(memory_line(device))
    checker = Checker(schema)
    eng = GQFastEngine(db)
    reg = MetricsRegistry()
    policy = RobustPolicy(registry=reg)
    rng = np.random.default_rng(args.seed)
    for shape in SHAPES:
        t0 = time.perf_counter()
        try:
            pq = eng.prepare(SG.PUBMED_QUERIES[shape])
        except QueryError as e:
            raise SmokeFailure(f"{shape}: prepare failed [{e.code}]: {e}") from e
        routes = pq.hop_routes(batched=True)
        log(f"{shape}: prepared ({pq.strategy}); hops: "
            + "; ".join(f"{h} {r}" for h, r in routes))
        n_req, walls = 0, []
        for b in range(args.batches + 1):
            single = b == args.batches
            p = params_for(shape, rng, schema, 1 if single else args.batch)
            tb = time.perf_counter()
            if single:
                one = {k: int(v[0]) for k, v in p.items()}
                outcomes = [run_with_policy(pq, one, policy=policy)]
                rows = [one]
            else:
                outcomes = run_batch_with_policy(pq, p, policy=policy)
                rows = [{k: int(v[i]) for k, v in p.items()}
                        for i in range(args.batch)]
            walls.append(time.perf_counter() - tb)
            for oc, row in zip(outcomes, rows):
                if oc.status != "ok" or oc.rung != "active":
                    detail = oc.error.to_dict() if oc.error is not None else {}
                    raise SmokeFailure(
                        f"{shape} {row}: outcome {oc.status} on rung "
                        f"{oc.rung} (demotions {list(oc.demotions)}) {detail}")
                checker.check(shape, row, oc.value)
                n_req += 1
        dt = time.perf_counter() - t0
        args_b = [np.zeros(args.batch, np.int32) for _ in pq.param_names]
        kernels = count_kernels(pq.batched_fn, args_b)
        kernels_single = count_kernels(pq.fn, [0] * len(pq.param_names))
        log(f"{shape}: {n_req} requests ok on rung active "
            f"({args.batches} batches of {args.batch} + 1 single) in "
            f"{dt:.1f}s incl. compile and reference; host wall per call "
            f"(first ones compile): batches "
            + ", ".join(f"{w:.3f}s" for w in walls[:-1])
            + f", single {walls[-1]:.3f}s; tpu_custom_call: batched {kernels}, "
            f"single {kernels_single}")
        if any(r.startswith("pallas") for _, r in routes) and kernels == 0:
            raise SmokeFailure(f"{shape}: plan routes hops to Pallas but the "
                               "batched executable holds no tpu_custom_call")
        if kernels_single == 0:
            raise SmokeFailure(f"{shape}: the single-query executable holds no "
                               "tpu_custom_call")
    demotions = {k: v for k, v in reg.counters_with_prefix("robust.demotions").items()
                 if v}
    if demotions:
        raise SmokeFailure(f"ladder demotions: {demotions}")
    log(f"robust.demotions: none; errors: "
        f"{json.dumps(reg.counters_with_prefix('robust.errors'))}")
    log(f"reference: {checker.checked} answers within rtol {RTOL} / atol "
        f"{ATOL}; worst error {checker.worst:.4g}× the tolerance")
    log(memory_line(device))


def run_four_chips(args, devices) -> None:
    import numpy as np

    from repro.core.engine import GQFastEngine
    from repro.data import synth_graph as SG
    from repro.launch.mesh import make_mesh

    if len(devices) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found {len(devices)}")
    schema, db = build_deployment(args.docs, args.seed)
    checker = Checker(schema)
    mesh = make_mesh((4,), ("data",))
    eng = GQFastEngine(db, mesh=mesh)
    rng = np.random.default_rng(args.seed)
    sharded = None
    for shape in SHAPES:
        t0 = time.perf_counter()
        pq = eng.prepare(SG.PUBMED_QUERIES[shape])
        log(f"{shape}: prepared ({pq.strategy}) in "
            f"{time.perf_counter() - t0:.1f}s")
        if sharded is None:
            sharded = pq.sharded_db
            check_shards(sharded, mesh)
        n_req, walls = 0, []
        for b in range(args.batches + 1):
            single = b == args.batches
            p = params_for(shape, rng, schema, 1 if single else args.batch)
            tb = time.perf_counter()
            if single:
                one = {k: int(v[0]) for k, v in p.items()}
                outs, rows = [pq(**one)], [one]
            else:
                outs = pq.execute_batch(**p)
                rows = [{k: int(v[i]) for k, v in p.items()}
                        for i in range(args.batch)]
            walls.append(time.perf_counter() - tb)
            for got, row in zip(outs, rows):
                checker.check(shape, row, got)
                n_req += 1
        log(f"{shape}: {n_req} requests on the 4-device mesh match the "
            f"reference, {time.perf_counter() - t0:.1f}s incl. compile and "
            "reference; host wall per call (first ones compile): batches "
            + ", ".join(f"{w:.3f}s" for w in walls[:-1])
            + f", single {walls[-1]:.3f}s")
    log(f"reference: {checker.checked} answers within rtol {RTOL} / atol "
        f"{ATOL}; worst error {checker.worst:.4g}× the tolerance")
    for d in devices[:4]:
        log(f"{d}: " + memory_line(d))


def check_shards(sdb, mesh) -> None:
    """Every edge array is split in four: one distinct quarter per device."""
    want = set(mesh.devices.flat)
    for (table, key), di in sdb.indexes.items():
        arr = di.src_ids
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        sizes = {s.data.shape[0] for s in shards}
        starts = sorted(s.index[0].start or 0 for s in shards)
        if devs != want or sizes != {arr.shape[0] // 4} or len(set(starts)) != 4:
            raise SmokeFailure(
                f"I_{table}.{key}: edges not split across the 4 devices "
                f"(devices {sorted(map(str, devs))}, shard sizes {sizes})")
        log(f"I_{table}.{key}: {arr.shape[0]:,} edges as 4 shards of "
            f"{arr.shape[0] // 4:,}, one per device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--docs", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--batches", type=int, default=2,
                    help="batches per shape before the single query")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print(f"FAIL: the repro package is not next to {Path(__file__).name} "
              "(run it from a checkout of the repository)", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import CacheCounter, enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)
    cache = CacheCounter()
    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform {d0.platform}, kind {d0.device_kind}, "
        f"count {len(devices)}; jax {jax.__version__}")
    if d0.platform != "tpu":
        print(f"FAIL: no TPU — JAX found platform {d0.platform!r} "
              f"({d0.device_kind}); this smoke test runs only on the chip",
              file=sys.stderr)
        return 1
    log(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args, devices)
        else:
            run_one_chip(args, d0)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses")
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
