"""Hop work counters (DESIGN.md §Observability): the batched frontier
executable counts, per executed hop, the rows the hop kernel streams and the
trips of its gather loop. Every count here is checked against a numpy
recount of ``kernels/fragment_spmv.hop_block``'s loops over the same edge
stream, for empty, sparse, dense and padded-batch frontiers (Pallas
interpret mode), and the answers with counting on are bit-identical to
those without."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor as X
from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.core.lower import HopOp
from repro.core.semiring import semiring_for
from repro.data.synth_graph import QUERY_AD, QUERY_AS, QUERY_SD, make_pubmed
from repro.kernels import active as A
from repro.kernels import ops as K
from repro.kernels.params import EDGE_BLOCK, LANES

ROWS = EDGE_BLOCK // LANES


def recount(w: np.ndarray, src: np.ndarray, skip: bool = True):
    """``(rows, gathers)`` of one hop kernel call over frontier ``w``
    ``[B, n_src]`` and the edge stream's source ids, by walking
    ``hop_block``'s loops: every block, every batch group of 8 rows, every
    128-edge row, one gather per active chunk between the chunks of the
    row's least and greatest source (padding sources read ``n_src``). A
    hop whose frontier is empty is skipped by the executor's early exit."""
    B, n_src = w.shape
    if not (w != 0).any():
        return 0, 0
    groups = -(-B // 8)
    nc = n_src // LANES + 1
    if skip:
        nz = np.zeros(nc * LANES, bool)
        nz[:n_src] = (w != 0).any(axis=0)
        flags = nz.reshape(nc, LANES).any(axis=1)
    else:
        flags = np.ones(nc, bool)
    ccnt = np.concatenate([[0], np.cumsum(flags)])
    E = src.shape[0]
    nb = max(1, -(-E // EDGE_BLOCK))
    s = np.full(nb * EDGE_BLOCK, n_src, np.int64)
    s[:E] = src
    rows = s.reshape(nb * ROWS, LANES)
    trips = 0
    for _ in range(groups):  # the kernel's loop order, literally
        for r in rows:
            trips += int(ccnt[(r.max() >> 7) + 1] - ccnt[r.min() >> 7])
    return nb * ROWS * groups, trips


@pytest.fixture(scope="module")
def db():
    schema = make_pubmed(n_docs=1500, n_terms=300, n_authors=400, seed=5)
    return GQFastDatabase(schema, account_space=False)


@pytest.fixture(scope="module")
def engine(db):
    return GQFastEngine(db, strategy="frontier")


def _first_pull_hop(pq) -> HopOp:
    for op in X.iter_flat_ops(pq.phys):
        if isinstance(op, HopOp) and op.pull is not None:
            return op
    raise AssertionError("no pull-stream hop")


def _pull_hop(pq, table: str, src_key: str) -> HopOp:
    for op in X.iter_flat_ops(pq.phys):
        if isinstance(op, HopOp) and (op.table, op.src_key) == (table, src_key):
            return op
    raise AssertionError(f"no hop {table}.{src_key}")


def _frontier(kind: str, n_src: int, B: int, rng) -> np.ndarray:
    w = np.zeros((B, n_src), np.float32)
    if kind == "sparse":
        for b in range(B):
            w[b, rng.choice(n_src, size=3, replace=False)] = 1.0
    elif kind == "dense":
        w[:] = (rng.random((B, n_src)) < 0.6).astype(np.float32)
    elif kind == "padded":  # a ragged batch padded by repeating its last row
        w[:, rng.choice(n_src, size=40, replace=False)] = 2.0
        w[3:] = w[2]
    return w


def _hop(op, w, counting: bool, skip: str = "auto"):
    """One hop of the batched interpreter, jitted: its output and, when
    counting, the decoded counters."""
    sr = semiring_for("sum")

    @jax.jit
    def run(w):
        log = X.HopLog(counting=counting)
        interp = X._BatchedFrontierInterp({}, sr, batch=w.shape[0],
                                          block_skipping=skip, hops=log)
        return interp.hop(op, w, lambda st: st), log.counts()

    out, counts = run(jnp.asarray(w))
    return np.asarray(out), counts


@pytest.mark.parametrize("skip", ["auto", "off"])
@pytest.mark.parametrize("kind,B", [("empty", 8), ("sparse", 8), ("dense", 8),
                                    ("sparse", 3), ("padded", 5), ("dense", 16)])
def test_hop_counters_match_kernel_loop_recount(engine, kind, B, skip):
    pq = engine.prepare(QUERY_AS)
    op = _first_pull_hop(pq)  # AS starts at the author: DA.Author->Document
    assert (op.table, op.src_key) == ("DA", "Author")
    n_src = engine.db.schema.domain_size("Author")
    w = _frontier(kind, n_src, B, np.random.default_rng(7))
    out, counts = _hop(op, w, counting=True, skip=skip)
    (h,) = counts.decode()
    src = np.asarray(op.pull.src_col.materialize())
    rows, gathers = recount(w, src, skip=skip != "off")
    assert (h["rows"], h["gathers"]) == (rows, gathers)
    assert h["label"].startswith("h0:") and h["label"].endswith(":pull")
    if kind == "empty":
        assert rows == gathers == 0
    plain, none = _hop(op, w, counting=False, skip=skip)
    assert none.slots == () and np.asarray(none.values).shape == (0,)
    assert np.array_equal(out, plain)  # counting never changes the answer


@pytest.mark.parametrize("kind,B", [("sparse", 8), ("dense", 8), ("padded", 5)])
@pytest.mark.parametrize("table,dst_key", [("DT", "Term"), ("DA", "Author")])
def test_source_ordered_counters_match_kernel_loop_recount(engine, table, dst_key,
                                                           kind, B):
    """AS's Doc→Term and Doc→Author hops read source-ordered pull streams:
    the gathers counted from their row ranges are the trips of the kernel's
    loop over the permuted rows — fewer than over the same blocks in CSR
    order."""
    op = _pull_hop(engine.prepare(QUERY_AS), table, "Doc")
    stream = engine.db.device.index(table, dst_key).pull
    assert op.pull.src_col is stream.src_col
    w = _frontier(kind, engine.db.schema.domain_size("Document"), B,
                  np.random.default_rng(11))
    _, counts = _hop(op, w, counting=True)
    (h,) = counts.decode()
    rows, gathers = recount(w, np.asarray(op.pull.src_col.materialize()))
    assert (h["rows"], h["gathers"]) == (rows, gathers)
    csr_rows, csr_gathers = recount(
        w, np.asarray(engine.db.device.index(table, dst_key).dst_ids))
    assert rows == csr_rows and gathers < csr_gathers


@pytest.fixture(scope="module")
def dense_db(db):
    dense = GQFastDatabase(db.schema, account_space=False, device_encodings="dense")
    X.attach_pull_streams(dense.device)
    return dense


def test_pull_stream_blocks_hold_the_csr_edges_sorted_by_source(dense_db):
    """Each EDGE_BLOCK block of a pull stream holds the CSR block's edges
    sorted by (source, destination); in the kernel's layout the last
    block's padding stays at its end with source ``n_src``."""
    from repro.kernels.fragment_spmv import HopCfg, edge_operands

    for (table, key), di in dense_db.device.indexes.items():
        p = di.pull
        src, dst = np.asarray(p.src_col.materialize()), np.asarray(p.dst.array)
        csr_src, csr_dst = np.asarray(di.dst_ids), np.asarray(di.src_ids)
        E = src.shape[0]
        cols = [(np.asarray(p.measure_cols[m].materialize()),
                 np.asarray(c.materialize())) for m, c in di.measure_cols.items()]
        for b in range(0, E, EDGE_BLOCK):
            sl = slice(b, b + EDGE_BLOCK)
            got = zip(src[sl].tolist(), dst[sl].tolist(), *(g[sl].tolist() for g, _ in cols))
            want = zip(csr_src[sl].tolist(), csr_dst[sl].tolist(),
                       *(c[sl].tolist() for _, c in cols))
            assert sorted(got) == sorted(want)
            assert (np.lexsort((dst[sl], src[sl])) == np.arange(src[sl].shape[0])).all()
        assert np.array_equal(p.row_src_min, A.row_ranges(src)[0])
        rel = dense_db.schema.relationships[table]
        n_src = dense_db.schema.domain_size(rel.fk_entity(rel.other_fk(key)))
        ops, nb = edge_operands(HopCfg(n_src, 1), E, src, dst, None, None)
        last = np.asarray(ops[0]).reshape(nb, EDGE_BLOCK)[-1]
        tail = E - (nb - 1) * EDGE_BLOCK
        assert np.array_equal(last[:tail], src[(nb - 1) * EDGE_BLOCK:])
        assert (last[tail:] == n_src).all()


def test_pull_order_of_source_sorted_blocks_is_the_csr_order():
    """Where every block of an index is sorted by source as it stands, the
    stable sort keeps the CSR order exactly, so the stream costs no loop
    trip more than the CSR edges, and the answers match the reference."""
    from repro.core.reference import run_sql
    from repro.core.schema import EntityTable, RelationshipTable, Schema

    doc = np.repeat(np.arange(2048), 4)
    term = np.arange(doc.shape[0]) // 8
    schema = Schema(
        entities={"Document": EntityTable("Document", 2048),
                  "Term": EntityTable("Term", int(term.max()) + 1)},
        relationships={"DT": RelationshipTable(
            "DT", "Doc", "Term", "Document", "Term",
            {"Doc": doc, "Term": term, "Fre": 1 + term % 5})},
    )
    eng = GQFastEngine(GQFastDatabase(schema, account_space=False))
    di = eng.db.device.index("DT", "Doc")
    src, dst = np.asarray(di.dst_ids), np.asarray(di.src_ids)
    assert np.array_equal(A.block_source_order(src), np.arange(src.shape[0]))
    assert np.array_equal(np.asarray(di.pull.src_col.materialize()), src)
    assert np.array_equal(np.asarray(di.pull.dst.array), dst)
    q = "SELECT dt.Doc, SUM(dt.Fre) FROM DT dt WHERE dt.Term = :t GROUP BY dt.Doc"
    pq = eng.prepare(q)
    assert pq.hop_routes() == [("DT.Term->Document", "pallas")]
    for t in (0, 77):
        assert np.array_equal(pq(t=t), run_sql(schema, q, {"t": t}))


def test_fused_region_counts_hop1_gathers_and_hop2_rows(engine):
    """SD's hops form one fused region: hop1 is counted over the seed
    frontier; hop2 reads the VMEM intermediate, so only its rows count."""
    pq = engine.prepare(QUERY_SD)
    fused = [op for op in pq.phys.ops if isinstance(op, X.FusedHopOp)]
    assert fused, "SD is expected to fuse"
    h1, h2 = fused[0].hops
    seeds = np.array([3, 17, 17, 900, 1499, 5, 3, 17])
    out, counts = pq.batched_fn.counted(seeds)
    c1, c2 = counts.decode()
    w = np.zeros((8, engine.db.schema.domain_size("Document")), np.float32)
    w[np.arange(8), seeds] = 1.0
    rows, gathers = recount(w, np.asarray(h1.pull.src_col.materialize()))
    assert (c1["rows"], c1["gathers"]) == (rows, gathers)
    assert c1["label"].endswith(":pull") and c2["label"].endswith(":fused")
    E2 = int(h2.pull.dst_ids.shape[0])
    assert c2["rows"] == A.n_edge_blocks(E2) * ROWS
    assert c2["gathers"] is None and c2["active_chunks"] is None
    assert np.array_equal(np.asarray(out), np.asarray(pq.batched_fn(seeds)))


@pytest.mark.parametrize("sql,params", [
    (QUERY_AS, {"a0": [3, 9, 9, 27, 81, 5, 5, 5]}),
    (QUERY_AD, {"t1": [1, 2, 3, 4, 5, 6, 7, 8], "t2": [2, 3, 4, 5, 6, 7, 8, 9]}),
])
def test_counted_answers_bit_identical_to_execute_batch(engine, sql, params):
    pq = engine.prepare(sql)
    args = [np.asarray(params[n]) for n in pq.param_names]
    out, counts = pq.batched_fn.counted(*args)
    assert np.array_equal(np.asarray(out), pq.execute_batch(**params))
    hops = counts.decode()
    assert [h["label"].split(":")[0] for h in hops] == [f"h{k}" for k in range(len(hops))]
    assert all(h["rows"] % ROWS == 0 for h in hops)


def test_xla_rung_counts_nothing(engine):
    pq = engine.prepare(QUERY_AS)
    fn = X.compile_frontier_batched(pq.device_db, pq.phys, use_pallas=False,
                                    fusion="off")
    _, counts = fn.counted(np.arange(8))
    assert counts.decode() == []


def test_streamed_blocks_follows_the_skip_rule():
    """Source-sorted hops: rows follow the traced tier's block list (all
    blocks for a dense frontier under 'auto', the surviving ones for a
    sparse one)."""
    E = 40 * EDGE_BLOCK
    src = np.sort(np.random.default_rng(0).integers(0, 10_000, E)).astype(np.int32)
    bmin, bmax = A.block_ranges(src)
    for hot, mode in [(np.arange(10), "auto"), (np.arange(10_000), "auto"),
                      (np.arange(10), "on"), (np.arange(10), "off")]:
        w = np.zeros((1, 10_000), np.float32)
        w[0, hot] = 1.0
        got = int(jax.jit(lambda w: K.streamed_blocks(
            w, "sum", E, (bmin, bmax), mode))(jnp.asarray(w)))
        flags = (bmax >= hot.min()) & (bmin <= hot.max())
        na = int(flags.sum())
        want = {"on": na, "off": 40,
                "auto": na if na <= max(1, int(A.SKIP_BLOCK_FRACTION * 40)) else 40}[mode]
        assert got == want, (mode, got, want)


def test_chunk_coverage_counts_rows_per_chunk():
    rng = np.random.default_rng(1)
    for E, n_src in [(1, 5), (130, 300), (EDGE_BLOCK + 7, 2000), (3 * EDGE_BLOCK, 129)]:
        src = rng.integers(0, n_src, E)
        cov = A.chunk_coverage(*A.row_ranges(src), E, n_src)
        nb = A.n_edge_blocks(E)
        s = np.full(nb * EDGE_BLOCK, n_src)
        s[:E] = src
        rows = s.reshape(-1, LANES)
        want = np.zeros(n_src // LANES + 1, np.int64)
        for r in rows:
            want[r.min() >> 7:(r.max() >> 7) + 1] += 1
        assert np.array_equal(cov, want)


@pytest.mark.parametrize("nc", [7, 7813, 200_000])
def test_gather_trips_stay_exact_past_int32(nc):
    """A dense frontier over a wide domain makes billions of trips (1M
    documents: up to 2.4e9 per DT hop); the int32 partial sums keep them
    exact."""
    from repro.kernels.fragment_spmv import gather_trips

    rng = np.random.default_rng(nc)
    cov = rng.integers(2**30, 2**31 - 1, nc)
    flags = rng.random(nc) < 0.7
    parts = np.asarray(gather_trips(jnp.asarray(flags), cov)).astype(np.int64)
    S = parts.shape[0] // 2
    assert int(parts[:S].sum() + (parts[S:].sum() << 16)) == int((cov * flags).sum())
    assert int((cov * flags).sum()) > 2**31
