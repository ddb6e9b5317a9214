"""Fault-tolerant query lifecycle tests (DESIGN.md §Robustness).

Covers the typed QueryError taxonomy, admission control + the prepared-query
LRU, the deadline machinery, the degradation ladder (including result
agreement across rungs), deterministic fault injection, and an in-process
chaos serve smoke.
"""
import numpy as np
import pytest

from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.data import synth_graph as SG
from repro.obs.metrics import MetricsRegistry
from repro.robust import (
    LADDER,
    AdmissionController,
    Deadline,
    DeadlineExceeded,
    ExecutionError,
    MemoryBudget,
    ParseError,
    PlanError,
    PreparedCache,
    QueryError,
    ResourceError,
    RetryPolicy,
    RobustPolicy,
    ValidationError,
    estimate_query_bytes,
    run_batch_with_policy,
    run_with_policy,
    wrap_execution_error,
)
from repro.robust import faults
from repro.robust.runner import rung_fn

# The xla rung sums the hop kernel's products by source-sorted segment sums;
# the kernel sums destination-sorted 128-edge rows through exact bf16 splits
# on the MXU. Same products, another order: the largest gap measured over
# SD/FSD/AS/AD on four data seeds and four bindings each is 1.54e-7
# relative (AS; ~1.3 f32 ulp), zeros agree exactly.
XLA_RTOL = 1e-6


@pytest.fixture(scope="module")
def pubmed():
    return SG.make_pubmed(n_docs=60, n_terms=40, n_authors=30, seed=0)


@pytest.fixture(scope="module")
def db(pubmed):
    return GQFastDatabase(pubmed)


@pytest.fixture(scope="module")
def engine(db):
    return GQFastEngine(db)


@pytest.fixture(scope="module")
def prepared_sd(engine):
    return engine.prepare(SG.QUERY_SD)


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------


def test_taxonomy_codes_and_compat():
    # every class carries a stable machine-readable code and keeps the
    # builtin-exception compatibility contract (existing callers' excepts)
    cases = [
        (ParseError, "PARSE", (SyntaxError,)),
        (PlanError, "PLAN", (ValueError,)),
        (ValidationError, "VALIDATION", (ValueError, TypeError)),
        (ResourceError, "ADMISSION_OR_RESOURCE", (RuntimeError,)),
        (DeadlineExceeded, "DEADLINE", (TimeoutError,)),
        (ExecutionError, "EXECUTION", (RuntimeError,)),
    ]
    for cls, _, bases in cases:
        e = cls("boom", extra=1)
        assert isinstance(e, QueryError)
        for b in bases:
            assert isinstance(e, b), (cls, b)
        assert e.code  # non-empty default code
        assert e.retryable in (True, False)
        d = e.to_dict()
        assert d["code"] == e.code and d["retryable"] == e.retryable
        assert d["context"]["extra"] == 1
        assert "boom" in str(e)


def test_with_context_setdefault_semantics():
    e = ExecutionError("x", op="HopOp")
    e.with_context(op="other", rung="scan")
    assert e.context["op"] == "HopOp"  # original context wins
    assert e.context["rung"] == "scan"


def test_wrap_execution_error_passthrough_and_foreign():
    orig = ValidationError("bad")
    assert wrap_execution_error(orig, rung="scan") is orig
    wrapped = wrap_execution_error(KeyError("k"), rung="scan")
    assert isinstance(wrapped, ExecutionError) and not wrapped.retryable
    assert isinstance(wrapped.__cause__, KeyError)


def test_prepare_failures_are_typed_with_query_context(engine):
    with pytest.raises(ParseError) as ei:
        engine.prepare("SELECT FROM x")
    assert ei.value.context.get("position") is not None
    with pytest.raises(PlanError) as ei:
        engine.prepare("SELECT x.A FROM Nope x WHERE x.A = 1")
    assert "query" in ei.value.context
    # unknown GROUP BY variable used to escape as a raw KeyError
    with pytest.raises(PlanError):
        engine.prepare(
            "SELECT dt.Doc, COUNT(*) FROM DT dt WHERE dt.Doc = 1"
            " GROUP BY zz.Doc"
        )


def test_param_validation(engine, prepared_sd):
    with pytest.raises(ValidationError, match="missing"):
        prepared_sd()
    with pytest.raises(ValidationError, match="unknown"):
        prepared_sd(d0=1, nope=2)
    pad = engine.prepare(SG.QUERY_AD)
    with pytest.raises(ValidationError, match="ragged"):
        pad._batch_args({"t1": [1, 2], "t2": [1]})
    with pytest.raises(ValidationError, match="scalar"):
        prepared_sd._batch_args({"d0": 3})
    # the taxonomy keeps execute_batch's historical TypeError contract
    with pytest.raises(TypeError, match="missing"):
        prepared_sd._batch_args({})


def test_bad_block_skipping_is_validation_error(engine):
    with pytest.raises(ValidationError, match="block_skipping"):
        engine.prepare(SG.QUERY_SD, block_skipping="sometimes")


# ---------------------------------------------------------------------------
# Admission control + prepared LRU
# ---------------------------------------------------------------------------


def test_estimate_monotonic_in_batch(prepared_sd):
    e1 = estimate_query_bytes(prepared_sd, 1)
    e64 = estimate_query_bytes(prepared_sd, 64)
    assert e1["resident_bytes"] == e64["resident_bytes"] > 0
    assert e64["working_bytes"] > e1["working_bytes"] > 0


def test_admission_admit_demote_reject(prepared_sd):
    reg = MetricsRegistry()
    est1 = estimate_query_bytes(prepared_sd, 1)["total_bytes"]
    est64 = estimate_query_bytes(prepared_sd, 64)["total_bytes"]
    # budget between the single and batched footprint → demote
    mid = AdmissionController(
        MemoryBudget(limit_bytes=int((est1 + est64) / 2 / 0.9)), reg
    )
    assert mid.decide(prepared_sd, 1).action == "admit"
    assert mid.decide(prepared_sd, 64).action == "demote"
    with pytest.raises(ResourceError):
        mid.admit(prepared_sd, 64)  # demote without allow_demote raises
    assert mid.admit(prepared_sd, 64, allow_demote=True).action == "demote"
    tiny = AdmissionController(MemoryBudget(limit_bytes=16), reg)
    assert tiny.decide(prepared_sd, 1).action == "reject"
    with pytest.raises(ResourceError) as ei:
        tiny.admit(prepared_sd, 1)
    assert ei.value.code == "ADMISSION"
    assert reg.counter("robust.admission_rejections").snapshot() >= 1
    assert reg.counter("robust.admission_demotions").snapshot() >= 1
    # no budget configured → everything admits
    free = AdmissionController(MemoryBudget(), reg)
    assert free.decide(prepared_sd, 4096).action == "admit"


def test_prepared_cache_lru():
    reg = MetricsRegistry()
    c = PreparedCache(capacity=2, registry=reg)
    c.put("a", 1), c.put("b", 2)
    assert c.get("a") == 1  # refresh: 'b' is now LRU
    c.put("c", 3)
    assert "b" not in c and "a" in c and "c" in c
    assert reg.counter("engine.prepared_cache_evictions").snapshot() == 1
    assert reg.counter("engine.prepared_cache_hits").snapshot() == 1
    with pytest.raises(ValueError):
        PreparedCache(capacity=0)


def test_engine_prepare_cache_bounded(db):
    eng = GQFastEngine(db, max_prepared=2)
    a = eng.prepare(SG.QUERY_SD)
    assert eng.prepare(SG.QUERY_SD) is a  # hit
    eng.prepare(SG.QUERY_AD)
    eng.prepare(SG.QUERY_FAD)  # evicts QUERY_SD
    assert eng.prepare(SG.QUERY_SD) is not a


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


def test_deadline_object():
    dl = Deadline(10_000.0)
    dl.check("nowhere")  # plenty of budget
    dl2 = Deadline(0.0)
    assert dl2.expired()
    with pytest.raises(DeadlineExceeded) as ei:
        dl2.check("op[HopOp]")
    assert ei.value.context["where"] == "op[HopOp]"


def test_deadline_trips_on_injected_delay(prepared_sd):
    plan = faults.FaultPlan(seed=1).add(
        faults.FaultSpec(site="runner.execute", mode="delay", delay_ms=60.0)
    )
    with faults.active(plan):
        oc = run_with_policy(prepared_sd, {"d0": 3}, deadline_ms=25.0)
    assert oc.status == "error" and oc.error.code == "DEADLINE"
    # without the delay the same deadline is generous
    oc = run_with_policy(prepared_sd, {"d0": 3}, deadline_ms=10_000.0)
    assert oc.status == "ok"


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------


def test_ladder_rungs_agree_sd(engine, prepared_sd):
    want = prepared_sd(d0=3)
    for rung in LADDER:
        got = np.asarray(rung_fn(prepared_sd, rung)(3))
        # integer-semiring results are bit-identical on every rung
        assert np.array_equal(got, want), rung


def test_ladder_rungs_agree_float_measures(engine):
    # float-measure chains: the kernel rungs (active/unfused/scan) are
    # bit-identical (same kernel, same ⊕ order); xla and fragment_loop sum
    # the same products in another order and agree to float tolerance
    # (the documented bit-identity caveat, DESIGN.md §Robustness)
    p = engine.prepare(SG.QUERY_AS)
    want = p(a0=2)
    for rung in ("unfused", "scan"):
        assert np.array_equal(np.asarray(rung_fn(p, rung)(2)), want), rung
    got = np.asarray(rung_fn(p, "xla")(2))
    np.testing.assert_allclose(got, want, rtol=XLA_RTOL)
    got = np.asarray(rung_fn(p, "fragment_loop")(2))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_retry_then_success_is_degraded(prepared_sd):
    reg = MetricsRegistry()
    plan = faults.FaultPlan(seed=2).add(
        faults.FaultSpec(site="runner.execute", mode="raise", max_fires=1)
    )
    pol = RobustPolicy(retry=RetryPolicy(max_attempts=3, base_ms=0.1),
                       registry=reg)
    with faults.active(plan):
        oc = run_with_policy(prepared_sd, {"d0": 3}, policy=pol)
    assert oc.status == "degraded" and oc.rung == "active"
    assert oc.attempts == 2 and not oc.demotions
    assert reg.counter("robust.retries").snapshot() == 1
    assert np.array_equal(oc.value, prepared_sd(d0=3))


def test_exhausted_retries_demote_down_ladder(prepared_sd):
    reg = MetricsRegistry()
    plan = faults.FaultPlan(seed=2).add(
        faults.FaultSpec(site="runner.execute", mode="raise", max_fires=3)
    )
    pol = RobustPolicy(retry=RetryPolicy(max_attempts=2, base_ms=0.1),
                       registry=reg)
    with faults.active(plan):
        oc = run_with_policy(prepared_sd, {"d0": 3}, policy=pol)
    assert oc.status == "degraded" and oc.demotions == ("active",)
    assert oc.rung == "unfused"
    assert reg.counter("robust.demotions.active").snapshot() == 1
    assert np.array_equal(oc.value, prepared_sd(d0=3))


def test_all_rungs_failing_returns_typed_error(prepared_sd):
    plan = faults.FaultPlan(seed=2).add(
        faults.FaultSpec(site="runner.execute", mode="raise")
    )
    pol = RobustPolicy(retry=RetryPolicy(max_attempts=1))
    with faults.active(plan):
        oc = run_with_policy(prepared_sd, {"d0": 3}, policy=pol)
    assert oc.status == "error" and not oc.ok
    assert oc.error.code == "FAULT_INJECTED"
    assert oc.demotions == LADDER


def test_run_with_policy_never_raises_on_bad_params(prepared_sd):
    oc = run_with_policy(prepared_sd, {"wrong": 1})
    assert oc.status == "error" and oc.error.code == "VALIDATION"


def test_batch_policy_matches_execute_batch(prepared_sd):
    arr = np.arange(6)
    ocs = run_batch_with_policy(prepared_sd, {"d0": arr})
    ref = prepared_sd.execute_batch(d0=arr)
    assert len(ocs) == 6 and all(o.status == "ok" for o in ocs)
    for i, o in enumerate(ocs):
        assert np.array_equal(o.value, ref[i])


def test_batch_admission_demotes_to_serial(prepared_sd):
    est1 = estimate_query_bytes(prepared_sd, 1)["total_bytes"]
    est64 = estimate_query_bytes(prepared_sd, 64)["total_bytes"]
    ctl = AdmissionController(
        MemoryBudget(limit_bytes=int((est1 + est64) / 2 / 0.9)),
        MetricsRegistry(),
    )
    pol = RobustPolicy(admission=ctl, registry=MetricsRegistry())
    arr = np.arange(64)
    ocs = run_batch_with_policy(prepared_sd, {"d0": arr}, policy=pol)
    ref = prepared_sd.execute_batch(d0=arr)
    assert all(o.status == "degraded" for o in ocs)  # served, but serially
    for i, o in enumerate(ocs):
        assert np.array_equal(o.value, ref[i])


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------


def test_fault_determinism_and_counting():
    def run(seed):
        plan = faults.FaultPlan(seed=seed).add(
            faults.FaultSpec(site="x", mode="raise", prob=0.5, max_fires=50)
        )
        seq = []
        with faults.active(plan):
            for _ in range(30):
                try:
                    faults.fire("x")
                    seq.append(0)
                except ExecutionError:
                    seq.append(1)
        return seq, plan

    s5, p5 = run(5)
    s5b, _ = run(5)
    s6, _ = run(6)
    assert s5 == s5b and s5 != s6
    assert p5.total_fires() == sum(s5)
    assert p5.stats()["x:raise"]["calls"] == 30


def test_fault_prefix_after_and_max_fires():
    plan = faults.FaultPlan().add(
        faults.FaultSpec(site="ops.", mode="raise", after=2, max_fires=1)
    )
    with faults.active(plan):
        faults.fire("ops.fragment_spmv")   # skipped (after)
        faults.fire("ops.fragment_spmm")   # skipped (after)
        with pytest.raises(ExecutionError) as ei:
            faults.fire("ops.fragment_spmv_packed")
        assert ei.value.retryable and ei.value.code == "FAULT_INJECTED"
        faults.fire("ops.fragment_spmv")   # max_fires exhausted
        faults.fire("other.site")          # prefix does not match
    assert plan.total_fires() == 1


def test_fire_is_noop_without_plan():
    faults.fire("ops.fragment_spmv")
    assert faults.corrupt("storage.materialize", 7) == 7


def test_storage_corrupt_then_restore(pubmed):
    db = GQFastDatabase(pubmed, device_encodings="packed")
    col = next(
        c for di in db.device.indexes.values()
        for c in ([di.dst_col] + list(di.measure_cols.values()))
        if getattr(c, "kind", None) in ("packed", "dict")
    )
    truth = np.asarray(col.materialize())
    plan = faults.FaultPlan().add(
        faults.FaultSpec(site="storage.materialize", mode="corrupt")
    )
    with faults.active(plan):
        bad = np.asarray(col.materialize())
    assert plan.total_fires() >= 1
    assert not np.array_equal(bad, truth)
    # the memo kept the true decode: corruption never persists
    assert np.array_equal(np.asarray(col.materialize()), truth)


def test_kernel_fault_at_trace_time_degrades_to_working_rung(pubmed, engine):
    # fresh engine: prepare must re-trace so the ops.* sites actually fire
    eng = GQFastEngine(GQFastDatabase(pubmed))
    plan = faults.FaultPlan(seed=3).add(
        faults.FaultSpec(site="ops.", mode="raise")
    )
    with faults.active(plan):
        pq = eng.prepare(SG.QUERY_AD)
        oc = run_with_policy(
            pq, {"t1": 5, "t2": 7},
            policy=RobustPolicy(retry=RetryPolicy(max_attempts=1)),
        )
    # Pallas dispatch is poisoned on every compile → the ladder must land on
    # a rung that doesn't dispatch Pallas at all (xla or fragment_loop)
    assert oc.ok and oc.rung in ("xla", "fragment_loop"), oc.to_dict()
    assert plan.total_fires() >= 1
    ref = engine.prepare(SG.QUERY_AD)(t1=5, t2=7)
    assert np.array_equal(oc.value, ref)


def test_fused_kernel_fault_degrades_to_unfused(pubmed, engine):
    # poison only the fused-region dispatch site: the ladder must shed the
    # fused kernels at the first demotion (the "unfused" rung re-runs the
    # same plan as per-hop kernel calls, keeping block skipping) and agree
    # bit-for-bit with an unpoisoned prepare
    from repro.core.fuse import has_fused

    eng = GQFastEngine(GQFastDatabase(pubmed))
    plan = faults.FaultPlan(seed=4).add(
        faults.FaultSpec(site="ops.fragment_spmv_fused", mode="raise")
    )
    with faults.active(plan):
        # fusion='on' runs the region fused whatever the VMEM budget says,
        # so the poisoned site is reached
        pq = eng.prepare(SG.QUERY_AS, fusion="on")
        assert has_fused(pq.phys)  # the poisoned site is on the active path
        oc = run_with_policy(
            pq, {"a0": 2},
            policy=RobustPolicy(retry=RetryPolicy(max_attempts=1)),
        )
    assert oc.ok and oc.status == "degraded", oc.to_dict()
    assert oc.rung == "unfused" and oc.demotions == ("active",)
    assert plan.total_fires() >= 1
    ref = engine.prepare(SG.QUERY_AS)(a0=2)
    assert np.array_equal(oc.value, ref)


# ---------------------------------------------------------------------------
# Chaos serve smoke (in-process micro version of the CI lane)
# ---------------------------------------------------------------------------


def test_chaos_serve_smoke(engine, prepared_sd):
    reg = MetricsRegistry()
    pol = RobustPolicy(retry=RetryPolicy(max_attempts=2, base_ms=0.1),
                       registry=reg)
    plan = (
        faults.FaultPlan(seed=9)
        .add(faults.FaultSpec(site="runner.execute", mode="raise",
                              prob=0.3, max_fires=6))
        .add(faults.FaultSpec(site="runner.execute", mode="delay",
                              delay_ms=5.0, prob=0.2))
    )
    rng = np.random.default_rng(0)
    outcomes = []
    with faults.active(plan):
        for _ in range(8):  # 8 micro-batches of 4 → 32 requests
            arr = rng.integers(0, 50, size=4)
            outcomes.extend(
                run_batch_with_policy(prepared_sd, {"d0": arr}, policy=pol)
            )
    assert len(outcomes) == 32
    assert all(o.status in ("ok", "degraded", "error") for o in outcomes)
    answered = [o for o in outcomes if o.ok]
    assert answered, "chaos must not take the service fully down"
    assert any(o.degraded for o in outcomes), "injected faults must degrade"
    # counters exported for the metrics artifact
    errs = reg.counters_with_prefix("robust.errors.")
    assert sum(errs.values()) > 0
    # structured wire form round-trips
    for o in outcomes:
        d = o.to_dict()
        assert d["status"] == o.status and "rung" in d


@pytest.mark.slow
def test_ladder_terminus_agrees_on_full_query_suite(engine):
    cases = {
        "SD": (SG.QUERY_SD, {"d0": 3}, True),
        "FSD": (SG.QUERY_FSD, {"d0": 3}, False),
        "AS": (SG.QUERY_AS, {"a0": 2}, False),
        "AD": (SG.QUERY_AD, {"t1": 2, "t2": 3}, True),
        "FAD": (SG.QUERY_FAD, {"t1": 2, "t2": 3}, True),
    }
    for name, (q, params, exact) in cases.items():
        p = engine.prepare(q)
        want = p(**params)
        args = [params[n] for n in p.param_names]
        for rung in LADDER:
            got = np.asarray(rung_fn(p, rung)(*args))
            if exact or rung not in ("xla", "fragment_loop"):
                assert np.array_equal(got, want), (name, rung)
            elif rung == "xla":
                np.testing.assert_allclose(got, want, rtol=XLA_RTOL)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
