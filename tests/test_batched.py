"""Batched multi-query execution (DESIGN.md §Batched serving).

``PreparedQuery.execute_batch`` must be *bit-identical* to a Python loop of
single-query calls — the batched SpMM path changes the schedule (one edge
stream serves B frontier rows) but not one float of per-row math. Covered
here: all strategies (frontier SpMM, fragment_loop vmap fallback, 1-device
distributed), all semirings (SUM/COUNT/MIN/MAX/AVG/EXISTS), packed and dense
device encodings, and batch sizes 1/3/64 — 3 exercises the ragged-pad bucket
boundary (pads to 4, pad rows sliced off), 64 an exact bucket. Plus the
kernel-level SpMM-vs-oracle sweep and the execute_batch validation contract.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import GQFastDatabase, GQFastEngine, batch_bucket
from repro.data import synth_graph as SG
from repro.kernels import ops, ref

N_DOCS, N_TERMS, N_AUTHORS = 300, 40, 120

AGG_SQL = """
SELECT dt2.Doc, {agg}
FROM DT dt1 JOIN DT dt2 ON dt1.Term = dt2.Term
WHERE dt1.Doc = :d0
GROUP BY dt2.Doc
"""


@pytest.fixture(scope="module")
def schema():
    return SG.make_pubmed(
        n_docs=N_DOCS, n_terms=N_TERMS, n_authors=N_AUTHORS, seed=9
    )


@pytest.fixture(scope="module")
def dbs(schema):
    return {
        "packed": GQFastDatabase(schema, account_space=False),
        "dense": GQFastDatabase(schema, account_space=False,
                                device_encodings="dense"),
    }


def _assert_batch_matches_loop(pq, B: int, rng, param_doms: dict[str, int]):
    params = {n: rng.integers(0, dom, size=B) for n, dom in param_doms.items()}
    got = pq.execute_batch(**params)
    loop = np.stack(
        [pq(**{n: int(v[i]) for n, v in params.items()}) for i in range(B)]
    )
    assert got.shape == loop.shape
    assert np.array_equal(got, loop), (
        f"batched != per-query loop at B={B} (max|Δ|="
        f"{np.abs(got - loop).max()})"
    )


# ---------------------------------------------------------------------------
# Kernel level: SpMM vs oracle vs per-row SpMV
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ["sum", "min", "max", "bool"])
def test_fragment_spmm_matches_spmv_rows(op):
    rng = np.random.default_rng(3)
    B, n_src, n_dst, E = 4, 150, 90, 6000
    W = rng.random((B, n_src)).astype(np.float32)
    src = rng.integers(0, n_src, E).astype(np.int32)
    dst = rng.integers(0, n_dst, E).astype(np.int32)
    m = rng.integers(1, 6, E).astype(np.float32)
    got = np.asarray(ops.fragment_spmm(W, src, dst, m, n_dst, op=op))
    rows = np.stack([
        np.asarray(ops.fragment_spmv(W[b], src, dst, m, n_dst, op=op))
        for b in range(B)
    ])
    assert np.array_equal(got, rows)
    oracle = np.asarray(
        ref.fragment_spmm_ref(jnp.asarray(W), jnp.asarray(src),
                              jnp.asarray(dst), jnp.asarray(m), n_dst, op=op)
    )
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)


def test_fragment_spmm_per_row_measures():
    """[B, E] measure streams (seed-scalar-dependent expressions) take the
    vmap'd XLA fallback; each row must equal its own SpMV."""
    rng = np.random.default_rng(4)
    B, n_src, n_dst, E = 3, 80, 60, 2000
    W = rng.random((B, n_src)).astype(np.float32)
    src = rng.integers(0, n_src, E).astype(np.int32)
    dst = rng.integers(0, n_dst, E).astype(np.int32)
    m = rng.random((B, E)).astype(np.float32)
    got = np.asarray(ops.fragment_spmm(W, src, dst, m, n_dst))
    for b in range(B):
        row = np.asarray(ops.fragment_spmv(W[b], src, dst, m[b], n_dst))
        np.testing.assert_allclose(got[b], row, rtol=1e-5, atol=1e-5)


def test_fragment_spmm_empty_relation():
    W = np.ones((2, 5), np.float32)
    e = np.zeros(0, np.int32)
    out = np.asarray(ops.fragment_spmm(W, e, e, np.zeros(0, np.float32), 7))
    assert out.shape == (2, 7) and (out == 0).all()


# ---------------------------------------------------------------------------
# Engine level: every semiring, batched == loop, bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("agg", [
    "SUM(dt1.Fre * dt2.Fre)", "COUNT(*)", "MIN(dt2.Fre)", "MAX(dt2.Fre)",
    "AVG(dt2.Fre)", "EXISTS(*)",
])
def test_semirings_batched(dbs, agg):
    eng = GQFastEngine(dbs["packed"], strategy="frontier")
    pq = eng.prepare(AGG_SQL.format(agg=agg))
    rng = np.random.default_rng(1)
    for B in (1, 3, 64):
        _assert_batch_matches_loop(pq, B, rng, {"d0": N_DOCS})


# ---------------------------------------------------------------------------
# Engine level: every strategy × encoding (incl. ragged bucket boundary)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["frontier", "fragment_loop", "auto"])
@pytest.mark.parametrize("enc", ["packed", "dense"])
def test_strategies_batched(dbs, strategy, enc):
    eng = GQFastEngine(dbs[enc], strategy=strategy)
    pq = eng.prepare(SG.QUERY_SD)
    rng = np.random.default_rng(2)
    assert batch_bucket(3) == 4  # B=3 really exercises the ragged pad
    for B in (3, 64):
        _assert_batch_matches_loop(pq, B, rng, {"d0": N_DOCS})


def test_distributed_batched(dbs):
    """1-device mesh: the shard_map body vmaps over the parameter vectors."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    for enc in ("packed", "dense"):
        eng = GQFastEngine(dbs[enc], mesh=mesh)
        pq = eng.prepare(SG.QUERY_SD)
        rng = np.random.default_rng(5)
        _assert_batch_matches_loop(pq, 3, rng, {"d0": N_DOCS})


def test_mask_seed_and_seed_scalar_batched(dbs):
    """AD seeds from an IN-INTERSECT mask (batched sub-programs); FSD carries
    a seed-scalar (d1.Year) into a downstream factor — both must batch."""
    eng = GQFastEngine(dbs["packed"], strategy="frontier")
    rng = np.random.default_rng(6)
    _assert_batch_matches_loop(
        eng.prepare(SG.QUERY_AD), 5, rng, {"t1": N_TERMS, "t2": N_TERMS}
    )
    _assert_batch_matches_loop(eng.prepare(SG.QUERY_FSD), 5, rng, {"d0": N_DOCS})


def test_hop_without_reverse_index_runs_source_sorted_kernel(schema):
    # a database holding only I_{DT.Doc}: the Doc→Term hop has no
    # destination-sorted copy, so it runs the hop kernel over its own
    # source-sorted edges (with block lists), single and batched
    from repro.core.planner import plan_query
    from repro.core.reference import NumpyQueryEngine
    from repro.core.sql import parse

    db = GQFastDatabase(schema, account_space=False)
    del db.device.indexes[("DT", "Term")]
    q = ("SELECT dt.Term, SUM(dt.Fre) FROM DT dt WHERE dt.Doc = :d0 "
         "GROUP BY dt.Term")
    pq = GQFastEngine(db).prepare(q)
    route = [("DT.Doc->Term", "pallas (source-sorted)")]
    assert pq.hop_routes() == route and pq.hop_routes(batched=True) == route
    assert "hop DT.Doc->Term: pallas (source-sorted)" in pq.explain()
    oracle = NumpyQueryEngine(schema)
    plan = plan_query(schema, parse(q))
    d0 = np.array([0, 5, 17])
    got = pq.execute_batch(d0=d0)
    for i, d in enumerate(d0):
        want = oracle.execute_plan(plan, {"d0": int(d)})
        assert (want != 0).any()
        np.testing.assert_allclose(pq(d0=int(d)), want, rtol=1e-6)
        np.testing.assert_array_equal(got[i], pq(d0=int(d)))


def test_query_topk_batch(dbs):
    eng = GQFastEngine(dbs["packed"], strategy="frontier")
    ids = [3, 7, 11]
    tops = eng.query_topk_batch(SG.QUERY_SD, k=4, d0=ids)
    assert len(tops) == 3
    for i, top in zip(ids, tops):
        assert top == eng.query_topk(SG.QUERY_SD, k=4, d0=i)


# ---------------------------------------------------------------------------
# execute_batch validation contract
# ---------------------------------------------------------------------------


def test_batch_bucket_policy():
    assert [batch_bucket(b) for b in (1, 2, 3, 5, 8, 9, 64)] == \
        [1, 2, 4, 8, 8, 16, 64]
    assert batch_bucket(65) == 128 and batch_bucket(129) == 192


def test_execute_batch_accepts_lists(dbs):
    eng = GQFastEngine(dbs["packed"], strategy="frontier")
    pq = eng.prepare(SG.QUERY_SD)
    a = pq.execute_batch(d0=[0, 1, 2])
    b = pq.execute_batch(d0=np.asarray([0, 1, 2]))
    assert np.array_equal(a, b)


def test_execute_batch_rejects_bad_inputs(dbs):
    eng = GQFastEngine(dbs["packed"], strategy="frontier")
    pq = eng.prepare(SG.QUERY_AD)
    with pytest.raises(ValueError, match="ragged"):
        pq.execute_batch(t1=[1, 2, 3], t2=[1, 2])
    with pytest.raises(ValueError, match="scalar"):
        pq.execute_batch(t1=5, t2=[1, 2])
    with pytest.raises(TypeError, match="missing"):
        pq.execute_batch(t1=[1, 2])
    with pytest.raises(ValueError, match="empty"):
        pq.execute_batch(t1=[], t2=[])
    with pytest.raises(ValueError, match="1-D"):
        pq.execute_batch(t1=np.zeros((2, 2)), t2=[1, 2])
