"""``BENCHMARK.json`` and everything it names, found by name: the cell's
configuration (its ``file``), its traffic mix (``bench/traffic/<traffic>.json``)
and the readers of its metrics (``bench/metrics/<base>.py``). No code here
or in the harness names a cell."""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass
class Metric:
    name: str
    unit: str
    reader: object  # module with read(run)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name.split('.')[0]}")


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(root: Path, name: str, doc: dict | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` (or of ``doc``)."""
    doc = doc if doc is not None else load_json(root / "BENCHMARK.json")
    found = [w for w in doc["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in doc['workloads']]}")
    w = found[0]
    entry = next(c for c in doc["configs"] if c["name"] == w["config"])
    e2e = [m for m in doc["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in doc["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(
        name=name,
        chips=w["chips"],
        config=load_json(root / entry["file"]),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[Metric(m["name"], m["unit"], reader(m["name"])) for m in e2e],
        per_layer=[Metric(m["name"], m["unit"], reader(m["name"])) for m in layer],
    )
