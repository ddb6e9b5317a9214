"""Exact percentiles and the rate arithmetic of the end-to-end readers,
with requests and batches that straddle the window's ends."""
import math

import pytest

from bench.loop import Batch, Record, Request
from bench.metrics import p50_ms, p95_ms, qps
from bench.stats import percentile


class View:
    def __init__(self, rec, gave_up_at=None):
        self.record = rec
        self.gave_up_at = gave_up_at if gave_up_at is not None else rec.t_close + 60


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 95) == 95
    assert percentile([7.0], 95) == 7.0
    assert percentile([3, 1, 2], 50) == 2
    assert percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        percentile([], 50)


def _closed_record():
    rec = Record(t0=10.0, t_close=20.0)
    # batches: two inside, one begun before the close and ending after it,
    # one begun after the close (not in the window)
    spans = [(10.0, 14.0, 8), (14.0, 18.0, 8), (18.0, 23.0, 5), (23.0, 24.0, 8)]
    for i, (s, e, n) in enumerate(spans):
        rec.batches.append(Batch("X", s, e, n, n - (i == 0)))
        rec.requests += [Request(len(rec.requests), "X", {}, math.nan, done=e,
                                 batch=i, status="ok") for _ in range(n)]
    rec.requests[0].status = "error"
    rec.close()
    return rec


def test_closed_window_ends_with_the_batch_begun_inside_it():
    rec = _closed_record()
    assert rec.t_end == 23.0
    assert len(rec.in_window()) == 21


def test_qps_counts_the_straddling_batch_by_its_share_inside():
    rec = _closed_record()
    # 7 answered (one error) + 8 + 5 x 2/5 of the batch [18, 23] inside
    # [10, 20]; the batch begun after the close counts nothing
    assert qps.read(View(rec)) == pytest.approx((7 + 8 + 2) / 10.0)


def test_open_latency_counts_requests_due_in_the_window():
    rec = Record(t0=100.0, t_close=110.0)
    rec.batches.append(Batch("X", 100.5, 101.0, 2, 2))
    rec.batches.append(Batch("X", 109.9, 111.0, 2, 2))
    rows = [
        (99.9, 101.0, "ok"),     # due before the window: not counted
        (100.2, 101.0, "ok"),    # 800 ms
        (109.8, 111.0, "ok"),    # due inside, answered after the close: 1200 ms
        (110.0, 111.0, "ok"),    # due at the close: outside
        (105.0, math.nan, ""),   # never answered: waited until the run gave up
    ]
    for i, (due, done, st) in enumerate(rows):
        rec.requests.append(Request(i, "X", {}, due, done=done, status=st,
                                    batch=-1 if st == "" else 0))
    rec.close()
    view = View(rec, gave_up_at=170.0)
    # latencies: 800, 1200, 65000 ms
    assert p50_ms.read(view) == pytest.approx(1200.0)
    assert p95_ms.read(view) == pytest.approx(65000.0)
    assert rec.t_end == 111.0


def test_open_window_with_no_batch_at_the_close_ends_at_the_close():
    rec = Record(t0=0.0, t_close=5.0)
    rec.batches.append(Batch("X", 1.0, 1.2, 1, 1))
    rec.close()
    assert rec.t_end == 5.0
