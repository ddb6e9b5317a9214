"""Trips of the hop kernel's gather loop per streamed row (one trip reads
one active 128-entry frontier chunk for a row whose sources span it), over
the hops whose gathers the program counts (every hop but the second of a
fused region): the sum of the ``gathers`` stat of the program's ``hops``
spans of the batches that started in the traced window, over the sum of
their ``gather_rows``."""
from bench.metrics._program import batches, nested


def read(run):
    bs = batches(run)
    if bs is None:
        return None
    found = nested(bs, "hops")
    rows = sum(sp.meta["gather_rows"] for sp in found)
    if not rows:
        return None
    return sum(sp.meta["gathers"] for sp in found) / rows
