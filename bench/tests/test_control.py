"""The control, the reference computed in bfloat16 in the program's place,
comes out not correct under each cell's limits, and the program's own
answers come out correct. Both cells run at 50,000 documents."""
import numpy as np
import pytest

from bench import control as C
from bench import spec as S
from bench.tests.common import ROOT


@pytest.mark.parametrize("workload,requests", [
    ("pubmed-m-1m.term-pairs", 200),
    ("pubmed-m-1m.dashboard", 40),
])
def test_control_is_not_correct(workload, requests):
    cell = S.cell(ROOT, workload)
    n = 50_000
    pub = cell.config["published"]
    cell.config.update(n_docs=n,
                       n_authors=round(n * pub["n_authors"] / pub["n_docs"]),
                       dt_rows=int(n * pub["dt_rows"] / pub["n_docs"]),
                       da_rows=int(n * pub["da_rows"] / pub["n_docs"]))
    for seed in (101, 102, 103):
        out = C.readings(cell, seed, requests)
        assert out["control_correct"] is False, out


def test_program_answers_are_correct(tiny_cell):
    """The reference, fed the program's answers at a small size, passes."""
    from bench.check import compare_requests, judge
    from bench.data import generate
    from bench.graph import Graph
    from bench.sut import System

    cell = tiny_cell("pubmed-m-1m.dashboard")
    cfg = cell.config
    data = generate(cfg["schema"], cfg, 11)
    reqs = C.first_requests(cell, data, 11, 16)
    system = System(data, {s: cfg["queries"][s]["sql"] for s in cfg["queries"]})
    for shape in {r.shape for r in reqs}:
        rows = [r for r in reqs if r.shape == shape]
        arrays = {k: np.asarray([r.params[k] for r in rows]) for k in rows[0].params}
        for r, oc in zip(rows, system.execute(shape, arrays)):
            r.value = oc.value
    numbers = compare_requests(Graph(data), cfg["schema"], cfg["queries"], reqs)
    numbers["failed"] = 0
    assert judge(numbers, cfg["limits"])[0], numbers
