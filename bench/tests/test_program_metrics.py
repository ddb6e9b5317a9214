"""The per-layer metrics that read what the program records of its own host
path (``bench/metrics/_program.py``): a traced run of each cell at a tiny
size on the CPU reports all four, finite; a program that records nothing
leaves them out without an error."""
import math

import jax
import pytest

from bench import run as R

NEW = ("dispatch_ms", "fetch_ms", "hop_rows", "hop_gathers_per_row")


def _traced(cell):
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_qps"] = 50
    return R.run_cell(cell, 2**32 + 17, 2.0, True, jax.devices()[:1],
                      {"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("workload,split", [("pubmed-m-1m.dashboard", "sat"),
                                            ("pubmed-m-1m.term-pairs", "rate")])
def test_traced_run_reports_the_program_metrics(tiny_cell, workload, split):
    out = _traced(tiny_cell(workload))
    assert out["correct"]
    m = out["metrics"]
    for base in NEW:
        v = m[f"{base}.{split}"]["value"]
        assert math.isfinite(v) and v > 0, (base, v)
    # a hop streams whole 4096-edge blocks: at least 32 rows per batch
    assert m[f"hop_rows.{split}"]["value"] * 1e3 >= 32 / 8


def test_a_program_without_the_record_gives_none(monkeypatch):
    """The parent of the change that added the record has no ``PROFILED``:
    each reader then returns None, which leaves its metric out."""
    import importlib
    from types import SimpleNamespace

    from repro.obs import trace as program

    run = SimpleNamespace(trace=object(), traced_requests=lambda: 5,
                          record=SimpleNamespace(t0=0.0, t_close=float("inf")))
    monkeypatch.setattr(program, "PROFILED", program.Tracer())  # records nothing
    readers = [importlib.import_module(f"bench.metrics.{b}") for b in NEW]
    assert [r.read(run) for r in readers] == [None] * len(NEW)
    monkeypatch.delattr(program, "PROFILED")
    assert [r.read(run) for r in readers] == [None] * len(NEW)
