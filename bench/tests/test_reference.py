"""The benchmark's plain reference answers as the program's own numpy
engine (``repro.core.reference``) does, at a small size, for every shape."""
import numpy as np
import pytest

from bench.check import reference_answers
from bench.data import generate
from bench.graph import Graph
from bench.sut import program_schema
from bench.traffic import Sampler, shapes_of, sql_params
from bench.tests.common import TINY


@pytest.mark.parametrize("workload", ["pubmed-m-1m.dashboard", "pubmed-m-1m.term-pairs"])
def test_reference_matches_program_engine(tiny_cell, workload):
    from repro.core.planner import plan_query
    from repro.core.reference import NumpyQueryEngine
    from repro.core.sql import parse

    cell = tiny_cell(workload)
    cfg = cell.config
    data = generate(cfg["schema"], cfg, 3)
    schema = program_schema(data)
    oracle = NumpyQueryEngine(schema, collapse=True)
    graph = Graph(data)
    queries = {s: cfg["queries"][s]["sql"] for s in shapes_of(cell.traffic)}
    sampler = Sampler(data, cell.traffic["params"],
                      {s: sql_params(q) for s, q in queries.items()})
    rng = np.random.default_rng(0)
    for shape, sql in queries.items():
        plan = plan_query(schema, parse(sql))
        params = [sampler.draw(rng, shape).params for _ in range(20)]
        got = reference_answers(graph, cfg["schema"], shape, params)
        for p, row in zip(params, got):
            want = oracle.execute_plan(plan, p)
            np.testing.assert_allclose(row, want, rtol=1e-12, atol=0, err_msg=shape)
        assert np.count_nonzero(got), shape
