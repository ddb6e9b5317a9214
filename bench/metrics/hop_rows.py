"""Rows of 128 edges the hop kernels streamed per request answered in the
traced window, in thousands: the sum of the ``rows`` stat of the program's
``hops`` spans (its hop work counters: streamed edge blocks × 32 rows ×
batch groups of 8, over every executed hop) of the batches that started in
the window, over the requests those batches answered."""
from bench.metrics._program import batches, nested


def read(run):
    bs = batches(run)
    n = run.traced_requests()
    if bs is None or not n:
        return None
    found = nested(bs, "hops")
    if not found:
        return None
    return sum(sp.meta["rows"] for sp in found) / n / 1e3
