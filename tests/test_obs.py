"""Observability subsystem tests (DESIGN.md §Observability).

Covers the tracer (nesting, exception safety, the allocation-free disabled
path), the metrics registry (histogram percentiles vs a numpy oracle, JSON
round-trip), and the profiling path (``PreparedQuery.profile`` bit-identical
to plain execution across all three strategies; ``explain(analyze=True)``
renders per-op timings and predicted-vs-observed hop fractions).
"""
import json

import numpy as np
import pytest

from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.data.synth_graph import QUERY_AD, QUERY_AS, QUERY_SD, make_pubmed
from repro.obs import metrics as M
from repro.obs import trace as T
from repro.obs.profile import mispredicted


# ---------------------------------------------------------------- tracing


def test_spans_nest_and_record_wall_time():
    with T.recording() as tr:
        with T.span("outer"):
            with T.span("inner_a"):
                pass
            with T.span("inner_b", key="v"):
                pass
    assert [s.name for s in tr.roots] == ["outer"]
    outer = tr.roots[0]
    assert [c.name for c in outer.children] == ["inner_a", "inner_b"]
    assert outer.wall_ms is not None and outer.wall_ms >= 0
    assert outer.children[1].meta["key"] == "v"
    # self time never exceeds total and never goes negative
    assert 0 <= outer.self_wall_ms() <= outer.wall_ms + 1e-9


def test_span_closes_and_flags_status_under_exception():
    with T.recording() as tr:
        with pytest.raises(ValueError):
            with T.span("boom"):
                with T.span("child"):
                    raise ValueError("x")
    boom = tr.roots[0]
    assert boom.status == "error:ValueError"
    assert boom.wall_ms is not None  # closed despite the exception
    assert boom.children[0].status == "error:ValueError"
    # the stack fully unwound: new spans attach at the root again
    with T.recording() as tr2:
        with T.span("after"):
            pass
    assert [s.name for s in tr2.roots] == ["after"]


def test_disabled_fast_path_allocates_nothing():
    assert T.current() is None and not T.enabled()
    # every disabled span() call returns the same shared singleton
    s1, s2 = T.span("a"), T.span("b", big="meta")
    assert s1 is s2 is T.NULL_SPAN
    assert not hasattr(s1, "__dict__")  # __slots__ = (): no per-call state
    with s1 as s:
        s.annotate(x=1)
        assert s.fence(42) == 42
    T.annotate(ignored=True)  # no open span, no tracer: must be a no-op


def test_recording_nests_and_restores():
    with T.recording() as outer:
        with T.span("o"):
            pass
        with T.recording() as inner:
            with T.span("i"):
                pass
        assert T.current() is outer  # outer tracer resumes
        with T.span("o2"):
            pass
    assert T.current() is None
    assert [s.name for s in outer.roots] == ["o", "o2"]
    assert [s.name for s in inner.roots] == ["i"]


def test_tracer_to_dict_serializes_tree():
    with T.recording() as tr:
        with T.span("root", arr=np.arange(3)) as sp:
            sp.annotate(n=3)
            with T.span("leaf"):
                pass
    d = tr.to_dict()
    json.dumps(d)  # JSON-safe: non-scalar meta stringified
    assert d["spans"][0]["name"] == "root"
    assert d["spans"][0]["meta"]["n"] == 3
    assert d["spans"][0]["children"][0]["name"] == "leaf"


# ---------------------------------------------------------------- metrics


def test_counter_and_gauge():
    reg = M.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)  # get-or-create returns the same metric
    reg.gauge("g").set(7)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 7.0


def test_histogram_exact_moments_and_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=2.0, sigma=1.5, size=5000)  # spread across buckets
    h = M.Histogram()
    h.observe_many(vals)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(vals.sum())
    assert h.min == vals.min() and h.max == vals.max()
    for q in (50, 95, 99):
        est, oracle = h.percentile(q), float(np.percentile(vals, q))
        # interpolation error is bounded by the containing bucket's width
        bi = np.searchsorted(np.asarray(h.bounds), oracle)
        lo = h.bounds[bi - 1] if bi > 0 else h.min
        hi = h.bounds[bi] if bi < len(h.bounds) else h.max
        assert abs(est - oracle) <= (hi - lo) + 1e-9, (q, est, oracle)


def test_histogram_edge_cases():
    h = M.Histogram(bounds=(1.0, 2.0, 4.0))
    assert np.isnan(h.percentile(50))
    h.observe(3.0)
    assert h.percentile(0) == h.percentile(100) == 3.0  # single value: exact
    h.observe(100.0)  # overflow bucket
    assert h.counts[-1] == 1
    assert h.percentile(100) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        M.Histogram(bounds=(2.0, 1.0))


def test_metrics_json_round_trip():
    reg = M.MetricsRegistry()
    reg.counter("reqs").inc(41)
    reg.gauge("occ").set(5.5)
    h = reg.histogram("lat")
    h.observe_many([0.1, 1.0, 12.0, 250.0, 9000.0])
    clone = M.MetricsRegistry.from_json(reg.to_json())
    assert clone.snapshot() == reg.snapshot()
    # and the empty-histogram shape survives too
    reg2 = M.MetricsRegistry()
    reg2.histogram("empty")
    assert M.MetricsRegistry.from_json(reg2.to_json()).snapshot() == reg2.snapshot()


# ---------------------------------------------------------------- profiling


@pytest.fixture(scope="module")
def small_db():
    schema = make_pubmed(n_docs=1500, n_terms=80, n_authors=400, seed=3)
    return GQFastDatabase(schema, account_space=False)


CASES = [
    ("frontier", QUERY_SD, {"d0": 17}),
    ("frontier", QUERY_AD, {"t1": 3, "t2": 7}),  # mask seed + semijoin
    ("fragment_loop", QUERY_SD, {"d0": 17}),     # scalar walk (ops fuse)
    ("fragment_loop", QUERY_AD, {"t1": 3, "t2": 7}),  # frontier fallback
]


@pytest.mark.parametrize("strategy,sql,params", CASES)
def test_profile_bit_identical_to_call(small_db, strategy, sql, params):
    eng = GQFastEngine(small_db, strategy=strategy)
    pq = eng.prepare(sql)
    plain = np.asarray(pq(**params))
    prof = pq.profile(reps=1, **params)
    # the profile result comes from the same compiled executable as __call__
    assert np.array_equal(np.asarray(prof.result), plain)
    assert prof.strategy == strategy
    assert prof.total_wall_ms > 0


def test_profile_distributed_bit_identical(small_db):
    from repro.launch.mesh import make_mesh

    eng = GQFastEngine(small_db, mesh=make_mesh((1,), ("data",)))
    pq = eng.prepare(QUERY_SD)
    plain = np.asarray(pq(d0=17))
    prof = pq.profile(reps=1, d0=17)
    assert np.array_equal(np.asarray(prof.result), plain)
    assert prof.strategy == "distributed"
    assert prof.timing_method == "prefix-delta"
    # prefix-delta times every op (nothing fuses away under shard_map)
    assert all(o.wall_ms is not None for o in prof.ops)


def test_profile_covers_every_ir_op_and_hops(small_db):
    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_AS)
    prof = pq.profile(reps=1, a0=5)
    assert len(prof.ops) == len(pq.phys.ops)
    measured = [o for o in prof.ops if not o.fused]
    assert measured, "eager-span walk must time at least the non-fused ops"
    # one HopProfile per hop estimate, with both fractions populated
    assert len(prof.hops) == len(pq.hop_estimates)
    for h in prof.hops:
        assert 0.0 <= h.observed_active_fraction <= 1.0
        assert h.est_active_fraction >= 0.0
    d = json.loads(prof.to_json())
    assert d["strategy"] == "frontier" and d["ops"] and d["hops"]


def test_explain_analyze_renders_timings_and_fractions(small_db):
    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_SD)
    plain = pq.explain()
    text = pq.explain(analyze=True, d0=17)
    assert plain in text  # analyze extends, never replaces, the static plan
    assert "analyze: total" in text
    assert "wall" in text and "kernel" in text
    assert "predicted vs observed active fraction" in text
    assert "est=" in text and "obs=" in text


def test_mispredict_classification():
    assert not mispredicted(0.1, 0.15)          # within 2x
    assert mispredicted(0.1, 0.30)              # observed 3x over
    assert mispredicted(0.1, 0.01)              # observed 10x under
    assert not mispredicted(0.0, 0.0)           # both empty: agree
    assert mispredicted(0.0, 0.5)               # predicted none, saw plenty
    assert not mispredicted(0.2, 0.4, factor=2.0)  # boundary is inclusive


def test_per_op_self_walls_sum_to_total(small_db):
    # the eager instrumented walk runs un-jitted, so its raw per-op walls can
    # be orders of magnitude above the compiled total; the profile must
    # rescale them so the self-wall column is consistent with total_wall_ms
    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_SD)
    prof = pq.profile(reps=3, d0=17)
    assert prof.timing_method == "eager-span-scaled"
    walls = [o.wall_ms for o in prof.ops if o.wall_ms is not None]
    assert walls, "at least the non-fused ops must carry a self wall"
    assert abs(sum(walls) - prof.total_wall_ms) <= max(
        1e-6 * prof.total_wall_ms, 1e-9
    )
    for o in prof.ops:
        if o.wall_ms is not None:  # raw eager measurement preserved per op
            assert o.meta["eager_wall_ms"] >= 0.0
            assert o.kernel_ms is None or o.kernel_ms <= o.wall_ms + 1e-9


def test_profile_feeds_strategy_calibration(small_db):
    eng = GQFastEngine(small_db, strategy="auto")
    pq = eng.prepare(QUERY_SD)
    assert pq.plan_sig and eng.calibration.get(pq.plan_sig) is None
    prof = pq.profile(reps=1, d0=17)
    obs = eng.calibration.get(pq.plan_sig)
    assert obs == [h.observed_active_fraction for h in prof.hops]
    # the store overrides the fanout model on the next strategy choice
    eng.calibration.record(pq.plan_sig, [0.01])
    assert eng._pick_strategy(pq.plan, pq.plan_sig) == "fragment_loop"
    eng.calibration.record(pq.plan_sig, [0.5])
    assert eng._pick_strategy(pq.plan, pq.plan_sig) == "frontier"


def test_strategy_mispredict_counter_increments(small_db):
    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_AD)  # semijoin hop: estimate is the trivial 1.0
    before = M.REGISTRY.counter("strategy_mispredict").value
    prof = pq.profile(reps=1, t1=3, t2=7)
    after = M.REGISTRY.counter("strategy_mispredict").value
    n_mis = sum(1 for h in prof.hops if h.mispredict)
    assert after - before == n_mis


def test_disabled_call_path_untouched(small_db):
    """With no tracer installed, __call__ takes the plain path (no span
    machinery) and execution under recording matches it exactly."""
    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_SD)
    plain = np.asarray(pq(d0=9))
    with T.recording() as tr:
        recorded = np.asarray(pq(d0=9))
    assert np.array_equal(plain, recorded)
    names = [s.name for s in tr.iter_spans()]
    assert "execute" in names


def test_prepare_emits_lifecycle_spans(small_db):
    eng = GQFastEngine(small_db, strategy="frontier")
    with T.recording() as tr:
        eng.prepare(QUERY_AS)
    names = [s.name for s in tr.iter_spans()]
    for phase in ("prepare", "parse", "plan", "lower", "compile"):
        assert phase in names, names
    prep = tr.roots[0]
    assert prep.name == "prepare"
    assert [c.name for c in prep.children] == ["parse", "plan", "lower", "compile"]


# ------------------------------------------------- the profiler's clock


def _repro_events(trace_dir):
    """Every ``repro.*`` host event of the newest profile under
    ``trace_dir``: (name, start ns, end ns, stats)."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(T.PREFIX):
                    s = float(ev.start_ns)
                    out.append((ev.name, s, s + float(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_runner_spans_reach_a_profiler_trace(small_db, tmp_path):
    """Under a profiler session (no recorder installed) the runner's spans
    are ``repro.*`` events: batch ⊃ attempt ⊃ dispatch, device_wait, fetch,
    then one ``repro.hops`` per batch carrying the hop counters; they also
    land in ``PROFILED``. Without a session nothing is recorded."""
    import jax

    from repro.robust import RobustPolicy, run_batch_with_policy

    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_AS)
    policy = RobustPolicy(registry=M.MetricsRegistry())
    batch = {"a0": np.array([3, 5, 5])}
    run_batch_with_policy(pq, batch, policy=policy)  # compile outside
    T.PROFILED.roots.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            outs = run_batch_with_policy(pq, batch, policy=policy)
    finally:
        jax.profiler.stop_trace()
    assert all(o.status == "ok" for o in outs)
    assert not T.profiling() and T.current() is None

    evs = _repro_events(str(tmp_path))
    by = lambda n: [e for e in evs if e[0] == T.PREFIX + n]  # noqa: E731
    batches = by("batch")
    assert len(batches) == 2
    assert not by("compile")
    for b in batches:
        assert b[3]["rows"] == 3 and b[3]["bucket"] == 4
        assert b[3]["query"] == pq.query_id
        (att,) = [e for e in by("attempt") if _inside(e, b)]
        assert att[3]["rung"] == "active" and att[3]["attempt"] == 1
        (args,) = [e for e in by("args") if _inside(e, b)]
        assert args[2] <= att[1]
        steps = [e for e in evs if _inside(e, att) and e[0] in
                 (T.PREFIX + "dispatch", T.PREFIX + "device_wait",
                  T.PREFIX + "fetch", T.PREFIX + "hops")]
        assert [e[0][len(T.PREFIX):] for e in steps] == [
            "dispatch", "device_wait", "fetch", "hops"]
        hops = steps[-1][3]
        assert hops["rows"] > 0 and hops["gather_rows"] > 0
        assert hops["h0"].startswith("DA.Author->Document:pull rows=")
    # the same tree, recorded in-process on perf_counter
    roots = [sp for sp in T.PROFILED.roots if sp.name == "batch"]
    assert len(roots) == 2
    att = roots[0].children[-1]
    assert [c.name for c in att.children] == [
        "dispatch", "device_wait", "fetch", "hops"]
    assert att.children[-1].meta["rows"] == hops["rows"]

    T.PROFILED.roots.clear()
    run_batch_with_policy(pq, batch, policy=policy)
    assert T.PROFILED.roots == []
    assert T.span("batch") is T.NULL_SPAN


def test_disabled_span_path_allocates_nothing():
    """No tracer, no profiler session: a span costs no memory at all."""
    import tracemalloc

    assert not T.tracing()
    for _ in range(10):  # warm any lazily created state
        with T.span("dispatch"):
            pass
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        with T.span("dispatch"):
            pass
    grown, peak = (m - base for m in tracemalloc.get_traced_memory())
    tracemalloc.stop()
    # 10,000 Spans would take megabytes, one live Span hundreds of bytes
    assert grown < 1024 and peak < 1024, (grown, peak)


def test_profiler_alone_keeps_the_compiled_walk(small_db, tmp_path):
    """A profiler session installs no recorder: the executor's eager per-op
    walk (``_walk_ir_recorded``) stays off and results are unchanged."""
    import jax

    from repro.core import executor as X

    eng = GQFastEngine(small_db, strategy="frontier")
    pq = eng.prepare(QUERY_SD)
    plain = np.asarray(pq(d0=9))
    calls = []
    orig = X._walk_ir_recorded
    X._walk_ir_recorded = lambda *a: calls.append(a) or orig(*a)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            traced = np.asarray(pq(d0=9))
        finally:
            jax.profiler.stop_trace()
    finally:
        X._walk_ir_recorded = orig
    assert calls == [] and np.array_equal(plain, traced)
