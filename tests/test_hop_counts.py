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
    raise AssertionError("no destination-sorted hop")


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
