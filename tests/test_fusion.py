"""Pipelined multi-hop fusion tests (DESIGN.md §Pipelined fusion).

Covers the IR fusion pass (region formation boundaries, recursion into mask
seeds, the unfuse inverse), fused-vs-unfused/oracle
bit-identity at the kernel level (dense/packed operands × every kernel op ×
every skip mode × B=1/8), and the engine surface (every SQL aggregate,
batched serving, the VMEM-budget auto fallback, explain/ladder integration).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.core.fragments import _pack_words
from repro.core.fuse import (
    fuse_plan,
    fusion_groups,
    has_fused,
    unfuse_plan,
)
from repro.core.lower import (
    DegreeFilterOp,
    EntityFilterOp,
    FusedHopOp,
    GroupOp,
    HopOp,
    PhysicalPlan,
    SeedOp,
)
from repro.data import synth_graph as SG
from repro.kernels import active, ops, ref
from repro.kernels.ops import FusedHopOperands
from repro.kernels.params import EDGE_BLOCK

OPS = ["sum", "min", "max", "bool"]
ZERO = {"sum": 0.0, "min": np.inf, "max": -np.inf, "bool": 0.0}


# ---------------------------------------------------------------------------
# IR pass: region formation
# ---------------------------------------------------------------------------


def _mk_hop(n_src: int, n_dst: int, E: int, seed: int, **kw) -> HopOp:
    from repro.storage import DenseColumn

    rng = np.random.default_rng(seed)
    src = np.sort(rng.integers(0, n_src, E)).astype(np.int32)
    dst = rng.integers(0, n_dst, E).astype(np.int32)
    indptr = np.searchsorted(src, np.arange(n_src + 1)).astype(np.int32)
    smin, smax = active.block_ranges(src)
    return HopOp(
        "T", f"K{seed}", "E2", n_dst, jnp.asarray(indptr), jnp.asarray(src),
        DenseColumn(jnp.asarray(dst)), block_src_min=smin, block_src_max=smax,
        **kw,
    )


def _mk_plan(ops_, agg="sum", out_dom=64):
    return PhysicalPlan(tuple(ops_), (), agg, out_dom, None)


def _seed(dom=64):
    return SeedOp("E0", dom, ids=(3,))


def test_two_hop_chain_fuses_with_trailing_group():
    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    p = _mk_plan([_seed(), h1, h2, GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert [type(o).__name__ for o in f.ops] == ["SeedOp", "FusedHopOp"]
    region = f.ops[1]
    assert region.members == (h1, h2, p.ops[3])
    assert region.n_mid == h1.dom_dst
    assert region.hops == (h1, h2) and region.group is p.ops[3]
    assert "Fused[" in f.op_signature()[1]
    assert fusion_groups(f) and "Hop(" in fusion_groups(f)[0]


def test_mid_mask_filter_joins_region():
    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    filt = EntityFilterOp("E1", const_mask=jnp.ones(48, jnp.float32))
    p = _mk_plan([_seed(), h1, filt, h2, GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert [type(o).__name__ for o in f.ops] == ["SeedOp", "FusedHopOp"]
    assert f.ops[1].mid_filters == (filt,)


def test_bare_single_hop_stays_unfused():
    p = _mk_plan([_seed(), _mk_hop(64, 64, 500, 1), GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert not has_fused(f)
    assert f.ops == p.ops


def test_one_hop_plus_mask_filter_fuses_degenerate():
    h1 = _mk_hop(64, 64, 500, 1)
    filt = EntityFilterOp("E2", const_mask=jnp.ones(64, jnp.float32))
    p = _mk_plan([_seed(), h1, filt, GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert isinstance(f.ops[1], FusedHopOp)
    assert f.ops[1].hops == (h1,) and f.ops[1].group is p.ops[3]


def test_degree_filter_ends_region():
    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    dfilt = DegreeFilterOp("T", "K", jnp.ones(48, jnp.int32))
    p = _mk_plan([_seed(), h1, dfilt, h2, GroupOp("E2", 64)])
    f = fuse_plan(p)
    # neither side of the DegreeFilterOp has a fusable run
    assert not has_fused(f)
    assert [type(o).__name__ for o in f.ops] == [
        "SeedOp", "HopOp", "DegreeFilterOp", "HopOp", "GroupOp",
    ]


def test_factor_or_param_filter_ends_region():
    from repro.core.lower import LCond

    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    cond = LCond(("attr", "E1", "x"), jnp.ones(48), ">", 0)
    filt = EntityFilterOp("E1", param_conds=(cond,))
    p = _mk_plan([_seed(), h1, filt, h2, GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert not has_fused(f)


def test_group_only_joins_as_plan_tail():
    # a GroupOp that is NOT the last op (mask sub-chain shape) stays outside
    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    p = _mk_plan([_seed(), h1, h2, GroupOp(None, 64),
                  EntityFilterOp("E2", const_mask=jnp.ones(64, jnp.float32))])
    f = fuse_plan(p)
    region = f.ops[1]
    assert isinstance(region, FusedHopOp) and region.group is None
    assert [type(o).__name__ for o in f.ops] == [
        "SeedOp", "FusedHopOp", "GroupOp", "EntityFilterOp",
    ]


def test_mask_seed_subprograms_fuse_recursively():
    sub = _mk_plan(
        [SeedOp("E0", 64, ids=(1,)), _mk_hop(64, 48, 500, 3),
         _mk_hop(48, 64, 600, 4), GroupOp(None, 64)], agg=None,
    )
    seed = SeedOp("E0", 64, ids=None, programs=(sub,))
    p = _mk_plan([seed, _mk_hop(64, 64, 500, 1), GroupOp("E2", 64)])
    f = fuse_plan(p)
    assert has_fused(f)  # only via the sub-program
    assert isinstance(f.ops[0].programs[0].ops[1], FusedHopOp)
    u = unfuse_plan(f)
    assert not has_fused(u)


def test_unfuse_is_exact_inverse():
    h1, h2 = _mk_hop(64, 48, 500, 1), _mk_hop(48, 64, 600, 2)
    filt = EntityFilterOp("E1", const_mask=jnp.ones(48, jnp.float32))
    p = _mk_plan([_seed(), h1, filt, h2, GroupOp("E2", 64)])
    u = unfuse_plan(fuse_plan(p))
    assert u.ops == p.ops  # same member objects, same order


# ---------------------------------------------------------------------------
# Kernel level: fused vs unfused vs oracle, bit-identical
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chain():
    """Two-hop chain spanning several edge blocks: hop1 E0→E1, hop2 E1→E2.
    hop2's length is deliberately not block-aligned (pad-edge handling)."""
    rng = np.random.default_rng(11)
    n0, n1, n2 = 512, 300, 256
    E1, E2 = 2 * EDGE_BLOCK, 2 * EDGE_BLOCK + 1000
    src1 = np.sort(rng.integers(0, n0, E1)).astype(np.int32)
    dst1 = rng.integers(0, n1, E1).astype(np.int32)
    m1 = rng.integers(1, 8, E1).astype(np.float32)
    src2 = np.sort(rng.integers(0, n1, E2)).astype(np.int32)
    dst2 = rng.integers(0, n2, E2).astype(np.int32)
    m2 = rng.integers(1, 8, E2).astype(np.float32)
    mask = (rng.random(n1) < 0.7).astype(np.float32)
    return dict(n0=n0, n1=n1, n2=n2, src1=src1, dst1=dst1, m1=m1,
                src2=src2, dst2=dst2, m2=m2, mask=mask)


def _operands(c, packed: bool):
    """(hop1, hop2, ref-kwargs) with dense or bit-packed dst/measure columns."""
    if not packed:
        h1 = FusedHopOperands(c["src1"], c["dst1"], c["m1"], None, c["n1"],
                              m_mode="dense")
        h2 = FusedHopOperands(c["src2"], c["dst2"], c["m2"], None, c["n2"],
                              m_mode="dense")
        rk = dict(dst1_width=0, m1_mode="dense", m1_width=0,
                  dst2_width=0, m2_mode="dense", m2_width=0)
        return h1, h2, rk
    w1 = int(c["n1"] - 1).bit_length()
    w2 = int(c["n2"] - 1).bit_length()
    mw = 3  # measures are < 8
    h1 = FusedHopOperands(
        c["src1"], _pack_words(c["dst1"], w1), _pack_words(c["m1"].astype(np.int64), mw),
        None, c["n1"], dst_width=w1, m_mode="packed", m_width=mw,
    )
    h2 = FusedHopOperands(
        c["src2"], _pack_words(c["dst2"], w2), _pack_words(c["m2"].astype(np.int64), mw),
        None, c["n2"], dst_width=w2, m_mode="packed", m_width=mw,
    )
    rk = dict(dst1_width=w1, m1_mode="packed", m1_width=mw,
              dst2_width=w2, m2_mode="packed", m2_width=mw)
    return h1, h2, rk


def _w(n, sl, op, B=None):
    shape = (n,) if B is None else (B, n)
    w = np.full(shape, ZERO[op], np.float32)
    if B is None:
        w[sl] = 2.0
    else:
        for b in range(B):
            w[b, b * 16:(b * 16) + 8] = 2.0
    return w


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("skip", ["off", "on", "auto"])
def test_fused_two_hop_bit_identical(chain, op, skip):
    c = chain
    w = _w(c["n0"], slice(0, 24), op)
    for packed in (False, True):
        h1, h2, rk = _operands(c, packed)
        for binarize in (False, True):
            want = np.asarray(ref.fragment_spmv_fused_ref(
                jnp.asarray(w), h1.src_ids, h1.dst, h1.measure, None,
                h2.src_ids, h2.dst, h2.measure, None, c["mask"],
                n_mid=c["n1"], n_dst=c["n2"], op=op, mid_binarize=binarize, **rk,
            ))
            got = np.asarray(ops.fragment_spmv_fused(
                w, h1, h2, c["mask"], op=op, mid_binarize=binarize,
                fusion="on", block_skipping=skip,
            ))
            np.testing.assert_array_equal(
                got, want, err_msg=f"packed={packed} binarize={binarize}"
            )
            off = np.asarray(ops.fragment_spmv_fused(
                w, h1, h2, c["mask"], op=op, mid_binarize=binarize,
                fusion="off", block_skipping=skip,
            ))
            np.testing.assert_array_equal(off, want)


@pytest.mark.parametrize("op", OPS)
def test_fused_batched_bit_identical(chain, op):
    c = chain
    B = 8
    W = _w(c["n0"], None, op, B=B)
    for packed in (False, True):
        h1, h2, rk = _operands(c, packed)
        want = np.asarray(ref.fragment_spmm_fused_ref(
            jnp.asarray(W), h1.src_ids, h1.dst, h1.measure, None,
            h2.src_ids, h2.dst, h2.measure, None, c["mask"],
            n_mid=c["n1"], n_dst=c["n2"], op=op, mid_binarize=False, **rk,
        ))
        got = np.asarray(ops.fragment_spmm_fused(
            W, h1, h2, c["mask"], op=op, fusion="on", block_skipping="auto",
        ))
        np.testing.assert_array_equal(got, want, err_msg=f"packed={packed}")


def test_fused_degenerate_one_hop(chain):
    c = chain
    w = _w(c["n0"], slice(0, 24), "sum")
    mask1 = (np.random.default_rng(3).random(c["n1"]) < 0.5).astype(np.float32)
    for packed in (False, True):
        h1, _, rk = _operands(c, packed)
        want = np.asarray(ref.fragment_spmv_fused_ref(
            jnp.asarray(w), h1.src_ids, h1.dst, h1.measure, None,
            None, None, None, None, mask1,
            n_mid=c["n1"], n_dst=c["n1"], op="sum",
            dst1_width=rk["dst1_width"], m1_mode=rk["m1_mode"],
            m1_width=rk["m1_width"],
        ))
        for skip in ("off", "on", "auto"):
            got = np.asarray(ops.fragment_spmv_fused(
                w, h1, None, mask1, op="sum", fusion="on", block_skipping=skip,
            ))
            np.testing.assert_array_equal(got, want)


def test_fused_inside_jit_traced_tier(chain):
    import jax

    c = chain
    h1, h2, rk = _operands(c, False)
    want = np.asarray(ref.fragment_spmv_fused_ref(
        jnp.asarray(_w(c["n0"], slice(0, 24), "sum")), h1.src_ids, h1.dst,
        h1.measure, None, h2.src_ids, h2.dst, h2.measure, None, c["mask"],
        n_mid=c["n1"], n_dst=c["n2"], op="sum", **rk,
    ))

    @jax.jit
    def f(w):
        return ops.fragment_spmv_fused(
            w, h1, h2, c["mask"], op="sum", fusion="on", block_skipping="auto",
        )

    np.testing.assert_array_equal(np.asarray(f(_w(c["n0"], slice(0, 24), "sum"))), want)


def test_auto_fusion_respects_vmem_budget(chain, monkeypatch):
    c = chain
    assert not ops._fusion_unfusable("auto", c["n1"])
    monkeypatch.setattr(ops, "FUSED_VMEM_BUDGET_BYTES", 4 * 8 * c["n1"] - 1)
    assert ops._fusion_unfusable("auto", c["n1"])
    assert not ops._fusion_unfusable("on", c["n1"])  # 'on' forces fused
    # over budget, auto degrades to the unfused composition — same bits
    h1, h2, rk = _operands(c, False)
    w = _w(c["n0"], slice(0, 24), "sum")
    want = np.asarray(ops.fragment_spmv_fused(
        w, h1, h2, c["mask"], op="sum", fusion="off", block_skipping="auto",
    ))
    got = np.asarray(ops.fragment_spmv_fused(
        w, h1, h2, c["mask"], op="sum", fusion="auto", block_skipping="auto",
    ))
    np.testing.assert_array_equal(got, want)


def test_fused_rejects_unknown_modes(chain):
    from repro.robust.errors import ValidationError

    c = chain
    h1, h2, _ = _operands(c, False)
    w = _w(c["n0"], slice(0, 8), "sum")
    with pytest.raises(ValidationError, match="fusion"):
        ops.fragment_spmv_fused(w, h1, h2, op="sum", fusion="bogus")
    with pytest.raises(ValidationError, match="block_skipping"):
        ops.fragment_spmv_fused(w, h1, h2, op="sum", block_skipping="bogus")


# ---------------------------------------------------------------------------
# Engine surface
# ---------------------------------------------------------------------------


Q_SCORE = """
SELECT dt2.Doc, {agg}
FROM DT dt1 JOIN DT dt2 ON dt1.Term = dt2.Term
WHERE dt1.Doc = :d0
GROUP BY dt2.ID
"""

AGG_CALLS = {
    "SUM": "SUM(dt1.Fre * dt2.Fre)", "COUNT": "COUNT(*)",
    "MIN": "MIN(dt1.Fre * dt2.Fre)", "MAX": "MAX(dt1.Fre * dt2.Fre)",
    "AVG": "AVG(dt1.Fre * dt2.Fre)", "EXISTS": "EXISTS(*)",
}


@pytest.fixture(scope="module")
def pm():
    return SG.make_pubmed(n_docs=1500, n_terms=80, n_authors=400, seed=5)


@pytest.fixture(scope="module")
def engine(pm):
    return GQFastEngine(GQFastDatabase(pm, account_space=False))


@pytest.fixture(scope="module")
def engine_dense(pm):
    return GQFastEngine(
        GQFastDatabase(pm, account_space=False, device_encodings="dense")
    )


@pytest.mark.parametrize("agg", list(AGG_CALLS))
def test_engine_fused_matches_unfused_all_aggs(engine, agg):
    q = Q_SCORE.format(agg=AGG_CALLS[agg])
    on = engine.prepare(q, fusion="on")
    off = engine.prepare(q, fusion="off")
    assert has_fused(on.phys) and not has_fused(off.phys)
    np.testing.assert_array_equal(on(d0=7), off(d0=7))
    assert (np.asarray(off(d0=7)) != 0).any(), "degenerate test: empty result"


@pytest.mark.parametrize("skip", ["off", "on", "auto"])
def test_engine_fused_matches_unfused_skip_modes(engine, skip):
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    on = engine.prepare(q, block_skipping=skip, fusion="on")
    off = engine.prepare(q, block_skipping=skip, fusion="off")
    np.testing.assert_array_equal(on(d0=7), off(d0=7))


def test_engine_fused_batched_matches_unfused(engine):
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    d0 = np.arange(8)
    on = engine.prepare(q, fusion="on").execute_batch(d0=d0)
    off = engine.prepare(q, fusion="off").execute_batch(d0=d0)
    np.testing.assert_array_equal(on, off)


def test_engine_auto_fuses_within_vmem_budget(engine):
    # 'auto' forms the same regions as 'on' and runs them fused while the
    # resident intermediate fits the VMEM budget: same plan, same bits
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    auto = engine.prepare(q)
    assert auto.fusion == "auto" and has_fused(auto.phys)
    assert fusion_groups(auto.phys) == fusion_groups(
        engine.prepare(q, fusion="on").phys
    )
    np.testing.assert_array_equal(
        auto(d0=7), engine.prepare(q, fusion="off")(d0=7)
    )


def test_engine_dense_encoding_fused(engine_dense):
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    on = engine_dense.prepare(q, fusion="on")
    assert has_fused(on.phys)
    np.testing.assert_array_equal(
        on(d0=7), engine_dense.prepare(q, fusion="off")(d0=7)
    )


def test_engine_four_hop_chain_fuses_pairwise(engine):
    # QUERY_AS: hops 1-2 fuse; the factor filter after hop 3 breaks the rest
    pq = engine.prepare(SG.QUERY_AS, fusion="on")
    regions = [op for op in pq.phys.ops if isinstance(op, FusedHopOp)]
    assert len(regions) == 1 and len(regions[0].hops) == 2
    np.testing.assert_array_equal(
        pq(a0=2), engine.prepare(SG.QUERY_AS, fusion="off")(a0=2)
    )


def test_distributed_and_fragment_loop_stay_unfused(pm):
    from repro.launch.mesh import make_mesh

    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    db = GQFastDatabase(pm, account_space=False)
    dist = GQFastEngine(db, mesh=make_mesh((1,), ("data",)))
    assert not has_fused(dist.prepare(q).phys)
    floop = GQFastEngine(db, strategy="fragment_loop")
    assert not has_fused(floop.prepare(SG.QUERY_SD).phys)


def test_prepare_rejects_unknown_fusion(engine):
    from repro.robust.errors import ValidationError

    with pytest.raises(ValidationError, match="fusion"):
        engine.prepare(Q_SCORE.format(agg=AGG_CALLS["SUM"]), fusion="bogus")


def test_fusion_modes_are_distinct_cache_entries(engine):
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    on = engine.prepare(q, fusion="on")
    assert engine.prepare(q, fusion="off") is not on
    assert engine.prepare(q, fusion="on") is on


def test_explain_reports_fusion(engine):
    q = Q_SCORE.format(agg=AGG_CALLS["SUM"])
    text = engine.prepare(q, fusion="on").explain()
    assert "fusion: on" in text
    assert "fused region:" in text and "Hop(" in text
    assert "FusedHopOp" in text


def test_profile_fused_plan_covers_all_hops(engine):
    # a fused plan still reports one HopProfile per member hop, and the
    # region's single span carries the member list
    pq = engine.prepare(Q_SCORE.format(agg=AGG_CALLS["SUM"]), fusion="on")
    prof = pq.profile(reps=1, d0=7)
    assert len(prof.hops) == 2
    assert len(prof.ops) == len(pq.phys.ops)
    fused_ops = [o for o in prof.ops if o.meta.get("fused")]
    assert fused_ops and fused_ops[0].meta.get("members")
