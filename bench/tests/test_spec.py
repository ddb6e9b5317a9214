"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix and metric is found by its name, and the file keeps to the
benchmark's contract."""
import json
import re

import pytest

from bench import spec as S
from bench.tests.common import ROOT

DOC = json.load(open(ROOT / "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in DOC["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = S.cell(ROOT, workload)
    w = next(x for x in DOC["workloads"] if x["name"] == workload)
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"] == cell.config["chips"]
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.reader.read), m.name
    for m in cell.per_layer:
        spec = next(x for x in DOC["per_layer"] if x["name"] == m.name)
        assert spec["moves"] in e2e
    for shape in cell.traffic.get("cycle") or cell.traffic["mix"]:
        assert cell.config["queries"][shape]["answer"] in ("integer", "real")


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        S.cell(ROOT, "no-such.cell")


def test_contract_of_the_file():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in DOC[k]]
    assert len(set(x["name"] for x in DOC["end_to_end"] + DOC["per_layer"])) == \
        len(DOC["end_to_end"]) + len(DOC["per_layer"])
    for n in names:
        assert NAME.match(n), n
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(json.load(open(ROOT / c["file"])))
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len(json.dumps(DOC)) < 64 * 1024
