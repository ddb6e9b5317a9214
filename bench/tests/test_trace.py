"""The trace reducer, on a small trace recorded on a v5e chip
(``data/dashboard.xplane.pb.gz``, made by ``record_trace.py``: one cycle of
the dashboard's five shapes at 20,000 documents) and on made-up intervals."""
import re

import numpy as np
import pytest

from bench import trace as T
from bench.tests.common import ROOT

FIXTURE = ROOT / "bench/tests/data/dashboard.xplane.pb.gz"
KERNELS = re.compile(r"gqfast_(hop|fused_hops)(\.\d+)?$")


@pytest.fixture(scope="module")
def tr():
    return T.load(str(FIXTURE))


def test_fixture_holds_one_device_and_the_bench_spans(tr):
    assert len(tr.busy) == 1
    names = [n for n, _, _ in tr.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.run_batch") == 5
    assert names.count("bench.collect") == 5


def test_busy_union_matches_a_timeline(tr):
    lo, hi = tr.window()
    # brute force: mark each 100 ns tick covered by any op
    ticks = np.zeros(int((hi - lo) // 100) + 1, bool)
    for _, s, e, _ in tr.ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            ticks[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    want = ticks.sum() * 100
    got = T.busy_ns(tr, lo, hi)
    assert got == pytest.approx(want, rel=2e-3)
    assert 0 < got <= hi - lo


def test_kernel_self_time_within_busy_and_conditionals_hold_none(tr):
    lo, hi = tr.window()
    kern = T.op_ns(tr, KERNELS, lo, hi)
    assert 0 < kern <= T.busy_ns(tr, lo, hi)
    # every kernel runs inside the runner's span of its batch, up to the
    # skew between the device's clock and the host's (under 1 ms here)
    spans = [(s - 1e6, e + 1e6) for n, s, e in tr.spans if n == "bench.run_batch"]
    for name, s, e, _ in tr.ops:
        if KERNELS.match(name):
            assert any(a <= s and e <= b for a, b in spans), name
    top = dict(T.top_ops(tr, lo, hi, 50))
    assert max(top, key=top.get).startswith("gqfast_")
    assert sum(v for k, v in top.items() if k.startswith("cond")) < 1e-3


def test_gaps_are_named_by_the_span_open_at_them(tr):
    lo, hi = tr.window()
    gaps = T.gaps(tr.busy[0].intervals, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - T.busy_ns(tr, lo, hi))
    named = T.idle_gaps(tr, lo, hi, n=len(gaps))
    assert [d for _, d in named] == sorted((d for _, d in named), reverse=True)
    for (s, e), (name, _) in zip(sorted(gaps, key=lambda g: g[0] - g[1]), named):
        mid = (s + e) / 2
        inside = [n for n, a, b in tr.spans if a <= mid <= b and n != "bench.window"]
        assert name in inside or (name == "idle" and not inside)


def test_merge_gaps_and_self_times():
    assert T.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    b = T.Busy([(0, 10), (20, 30), (25, 40)])
    assert b.covered(5, 25) == 10
    assert b.covered(-5, 100) == 30
    assert b.covered(11, 19) == 0
    assert T.gaps(b.intervals, 0, 50) == [(10, 20), (40, 50)]
    # a conditional [0, 10] holding a kernel [1, 9], then an op [12, 13]
    assert T.self_times([(0, 10), (1, 9), (12, 13)]) == [2, 8, 1]
    assert T.op_name("%gqfast_hop.2 = f32[8,10]{1,0} custom-call(%x)") == "gqfast_hop.2"


def test_span_at_picks_the_innermost():
    tr = T.Trace(spans=[("bench.window", 0, 100), ("bench.run_batch", 10, 50),
                        ("bench.wait", 60, 70)])
    assert T.span_at(tr, 20) == "bench.run_batch"
    assert T.span_at(tr, 65) == "bench.wait"
    assert T.span_at(tr, 80) == "idle"
