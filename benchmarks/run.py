# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: python -m benchmarks.run [table3 table4 ...]

Each module reproduces one paper table/figure (DESIGN.md §8); the roofline
summary reads the dry-run artifacts (EXPERIMENTS.md §Roofline). Besides the
CSV stream, every suite writes an ``artifacts/bench/BENCH_<suite>.json``
artifact (name, us_per_call, derived + structured fields such as device
bytes) — the machine-readable perf trajectory CI accumulates per commit."""
from __future__ import annotations

import json
import os
import sys
import time

ARTIFACT_DIR = os.path.join("artifacts", "bench")


#: Repo-root consolidated perf file: suite → headline metrics, merged across
#: invocations (running one suite updates only its entry) so the perf
#: trajectory is tracked in-repo across PRs.
PERF_FILE = "BENCH_perf.json"


def _write_artifact(suite: str, records: list[dict], seconds: float,
                    error: str | None) -> None:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    payload = {"suite": suite, "seconds": round(seconds, 1), "records": records}
    if error:
        payload["error"] = error
    with open(os.path.join(ARTIFACT_DIR, f"BENCH_{suite}.json"), "w") as f:
        json.dump(payload, f, indent=1)


def _run_meta(git_sha: str | None) -> dict:
    """Provenance stamp for a suite entry: device kind, jax version, and the
    git SHA the caller passed in (``--git-sha=`` / ``BENCH_GIT_SHA``; only
    falls back to asking git when neither is given)."""
    meta: dict = {"stamped_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    try:
        import jax

        meta["jax_version"] = jax.__version__
        meta["device"] = jax.devices()[0].device_kind
        meta["backend"] = jax.default_backend()
    except Exception as e:  # noqa: BLE001
        meta["jax_version"] = f"unavailable: {e}"
    sha = git_sha or os.environ.get("BENCH_GIT_SHA")
    if not sha:
        import subprocess

        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10, check=True,
            ).stdout.strip()
        except Exception:  # noqa: BLE001
            sha = "unknown"
    meta["git_sha"] = sha
    return meta


def _update_perf_summary(suite: str, records: list[dict], seconds: float,
                         error: str | None, meta: dict,
                         known_suites=()) -> None:
    summary: dict = {}
    if os.path.exists(PERF_FILE):
        try:
            with open(PERF_FILE) as f:
                summary = json.load(f)
        except (OSError, json.JSONDecodeError):
            summary = {}
    suites = summary.setdefault("suites", {})
    # staleness: a suite entry is replaced wholesale (metric keys the suite no
    # longer emits disappear), and entries for suites the harness no longer
    # knows about are dropped entirely
    if known_suites:
        for stale in [k for k in suites if k not in known_suites]:
            del suites[stale]
    entry: dict = {
        "seconds": round(seconds, 1),
        # explicit outcome marker: a failed suite still writes its partial
        # records above, so consumers must not read presence as success
        "status": "failed" if error else "ok",
        "meta": meta,
        "metrics": {r["name"]: r["us_per_call"] for r in records if "name" in r},
    }
    from .common import TRACES

    traces = {
        r["name"]: TRACES[r["name"]]
        for r in records if r.get("name") in TRACES
    }
    if traces:
        entry["traces"] = traces
    if error:
        entry["error"] = error
    suites[suite] = entry
    with open(PERF_FILE, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)


def main() -> None:
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(Path(__file__).resolve().parents[1])
    from . import (
        fig14_pipelining,
        perf_baseline,
        fig15_parallel,
        selectivity,
        snapshot_restore,
        table3_runtime,
        table4_space,
        table56_denseid,
        table8_encodings,
        table9_decode,
        throughput,
    )

    suites = {
        "table3": table3_runtime.run,
        "table4": table4_space.run,
        "table56": table56_denseid.run,
        "fig14": fig14_pipelining.run,
        "table8": table8_encodings.run,
        "table9": table9_decode.run,
        "fig15": fig15_parallel.run,
        "perf": perf_baseline.run,
        "throughput": throughput.run,
        "selectivity": selectivity.run,
        "snapshot": snapshot_restore.run,
    }
    from .common import RECORDS

    argv = sys.argv[1:]
    git_sha = None
    for a in list(argv):
        if a.startswith("--git-sha="):
            git_sha = a.split("=", 1)[1]
            argv.remove(a)
    picked = argv or list(suites)
    meta = _run_meta(git_sha)
    failed = []
    print("name,us_per_call,derived")
    for name in picked:
        t0 = time.time()
        start, err = len(RECORDS), None
        try:
            suites[name]()
        except Exception as e:  # noqa: BLE001
            err = f"{type(e).__name__}: {e}"
            failed.append(name)
            print(f"{name}/ERROR,0,{err}")
        dt = time.time() - t0
        _write_artifact(name, RECORDS[start:], dt, err)
        _update_perf_summary(name, RECORDS[start:], dt, err, meta,
                             known_suites=tuple(suites))
        print(f"# {name} done in {dt:.1f}s", file=sys.stderr)
    # roofline summary (if dry-run artifacts exist)
    try:
        from repro.roofline.analysis import load_records, roofline_from_record

        for rec in load_records("artifacts/dryrun"):
            if rec.get("status") != "ok" or rec.get("variant"):
                continue
            rl = roofline_from_record(rec)
            print(
                f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']},"
                f"{rl.bound_s*1e6:.1f},dominant={rl.dominant}"
            )
    except Exception as e:  # noqa: BLE001
        print(f"roofline/ERROR,0,{e}")
    if failed:
        # every suite still ran and wrote its artifact, but CI must go red
        sys.exit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
