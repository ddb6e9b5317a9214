"""Pull-stream edge order (DESIGN.md §4): the hop kernel reads each hop's
edges from the index keyed on its destination, block by block, and each
EDGE_BLOCK block sorted by source inside (``executor.PullStream``). Served
answers over those streams must match the plain reference for the five
dashboard queries and for the sum, min, max and bool semirings, over dense,
BCA-packed and dict-packed columns; fused and unfused runs must stay
bit-identical to each other; and a twin of the same database with its
streams in CSR order, and its snapshot round trip, must give the same
answers."""
import dataclasses

import numpy as np
import pytest

from repro.core import executor as X
from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.core.reference import run_sql
from repro.data import synth_graph as SG
from repro.kernels import active as A
from repro.storage import DenseColumn, restore_db, snapshot_db

N = 1500

ENCODINGS = {
    "dense": "dense",
    "packed": "packed",
    "dict": {("DT", k, "Fre"): "dict" for k in ("Doc", "Term")},
}

QUERIES = {
    "SD": (SG.QUERY_SD, {"d0": [5, 17, 230]}),
    "FSD": (SG.QUERY_FSD, {"d0": [5, 17, 230]}),
    "AS": (SG.QUERY_AS, {"a0": [0, 3, 41]}),
    "AD": (SG.QUERY_AD, {"t1": [0, 1, 2], "t2": [1, 3, 5]}),
    "FAD": (SG.QUERY_FAD, {"t1": [0, 1, 2], "t2": [1, 3, 5]}),
}
INTEGER_VALUED = {"SD", "AD", "FAD"}

Q_SCORE = """
SELECT dt2.Doc, {agg}
FROM DT dt1 JOIN DT dt2 ON dt1.Term = dt2.Term
WHERE dt1.Doc = :d0
GROUP BY dt2.Doc
"""
SEMIRINGS = {
    "sum": "SUM(dt1.Fre * dt2.Fre)",
    "min": "MIN(dt1.Fre * dt2.Fre)",
    "max": "MAX(dt1.Fre * dt2.Fre)",
    "bool": "EXISTS(*)",
}


@pytest.fixture(scope="module")
def schema():
    return SG.make_pubmed(n_docs=N, n_terms=N, n_authors=N, seed=11)


@pytest.fixture(scope="module")
def dbs(schema):
    out = {name: GQFastDatabase(schema, account_space=False, device_encodings=enc)
           for name, enc in ENCODINGS.items()}
    for db in out.values():
        X.attach_pull_streams(db.device)
    return out


def csr_twin(db: GQFastDatabase) -> GQFastDatabase:
    """The same database with every pull stream in CSR order."""
    dev = db.device
    indexes = {
        k: dataclasses.replace(di, pull=X.PullStream(
            DenseColumn(di.src_ids), di.dst_col, di.measure_cols,
            *A.row_ranges(np.asarray(di.dst_ids))))
        for k, di in dev.indexes.items()
    }
    return GQFastDatabase.from_parts(db.schema, db.host_indexes, X.DeviceDB(
        dev.schema, indexes, dev.entity_attrs, dev.host_indexes))


def _rows(params: dict) -> list[dict]:
    n = len(next(iter(params.values())))
    return [{k: int(v[i]) for k, v in params.items()} for i in range(n)]


def _check_reference(got, ref, integer: bool):
    assert (ref != 0).any(), "degenerate test: empty result"
    if integer:
        assert np.array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("enc", list(ENCODINGS))
def test_every_stream_is_a_permuted_copy(dbs, enc):
    """All four streams hold their own copies, in the encoding and width
    of the CSR columns they were permuted from."""
    for (t, k), di in dbs[enc].device.indexes.items():
        p = di.pull
        assert not np.array_equal(np.asarray(p.dst.array), np.asarray(di.src_ids))
        pairs = [(p.src_col, di.dst_col)] + [
            (p.measure_cols[m], c) for m, c in di.measure_cols.items()]
        for got, csr in pairs:
            assert type(got) is type(csr) and got is not csr
            assert getattr(got, "width", 0) == getattr(csr, "width", 0)
    if enc == "dict":
        fre = dbs[enc].device.indexes[("DT", "Doc")].pull.measure_cols["Fre"]
        assert fre.kind == "dict"


@pytest.mark.parametrize("enc", list(ENCODINGS))
@pytest.mark.parametrize("name", list(QUERIES))
def test_queries_match_reference(dbs, schema, name, enc):
    q, params = QUERIES[name]
    db = dbs[enc]
    eng = GQFastEngine(db)
    on = eng.prepare(q, fusion="on").execute_batch(**params)
    off = eng.prepare(q, fusion="off").execute_batch(**params)
    assert np.array_equal(on, off), "fused and unfused diverged"
    routes = {r for _, r in eng.prepare(q).hop_routes(batched=True)}
    assert routes == {"pallas"}
    csr = GQFastEngine(csr_twin(db)).prepare(q).execute_batch(**params)
    for i, row in enumerate(_rows(params)):
        ref = run_sql(schema, q, row)
        _check_reference(on[i], ref, name in INTEGER_VALUED)
        _check_reference(csr[i], ref, name in INTEGER_VALUED)


@pytest.mark.parametrize("enc", list(ENCODINGS))
@pytest.mark.parametrize("semiring", list(SEMIRINGS))
def test_semirings_match_reference(dbs, schema, semiring, enc):
    q = Q_SCORE.format(agg=SEMIRINGS[semiring])
    eng = GQFastEngine(dbs[enc])
    for d0 in (5, 230):
        on = eng.prepare(q, fusion="on")(d0=d0)
        off = eng.prepare(q, fusion="off")(d0=d0)
        assert np.array_equal(on, off), "fused and unfused diverged"
        # products of integer Fre: every semiring's answer is exact
        _check_reference(on, run_sql(schema, q, {"d0": d0}), integer=True)


def test_snapshot_round_trip_rebuilds_the_streams(dbs, tmp_path):
    db = dbs["packed"]
    snapshot_db(db, str(tmp_path))
    back = restore_db(str(tmp_path))
    X.attach_pull_streams(back.device)
    for key, di in db.device.indexes.items():
        p, q = di.pull, back.device.indexes[key].pull
        assert np.array_equal(np.asarray(q.dst.array), np.asarray(p.dst.array))
        assert np.array_equal(np.asarray(q.src_col.words), np.asarray(p.src_col.words))
        assert np.array_equal(q.row_src_min, p.row_src_min)
        assert np.array_equal(q.row_src_max, p.row_src_max)
    for q, params in QUERIES.values():
        want = GQFastEngine(db).prepare(q).execute_batch(**params)
        got = GQFastEngine(back).prepare(q).execute_batch(**params)
        assert np.array_equal(got, want)


def test_mesh_engine_builds_no_pull_stream(schema):
    """The edge-sharded path reads CSR shards only: an engine with a mesh
    leaves the database without pull streams (no copies on chip 0), and its
    answers still match the reference."""
    from repro.launch.mesh import make_mesh

    db = GQFastDatabase(schema, account_space=False)
    eng = GQFastEngine(db, mesh=make_mesh((1,), ("data",)))
    q, params = QUERIES["FAD"]
    got = eng.prepare(q).execute_batch(**params)
    assert all(di.pull is None for di in db.device.indexes.values())
    for i, row in enumerate(_rows(params)):
        _check_reference(got[i], run_sql(schema, q, row), integer=True)
