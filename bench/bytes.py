"""Useful bytes of a query: the least a hop must read from HBM to answer it.

For each hop of the shape's chain (``bench/reference/<schema>.py``) the
boolean support of the frontier is propagated on the host, the edges whose
source is in it are counted, and each such edge is charged the fixed-width
packed bytes it must carry: ceil(log2 n_dst) bits for its destination, plus
ceil(log2 distinct values) bits for a measure the hop reads. Padding rows of
a batch count nothing, and the count is the same whatever implements the
hop. Under fixed-width packing it is a lower bound: the program has no
entropy coding that could read fewer bytes.

The hop roofline is then ``useful bytes / peak HBM bytes/s`` over the
device's busy time (``bench/metrics/hop_roofline.py``)."""
from __future__ import annotations

import math

import numpy as np

from .graph import Graph
from .reference import Exact, shapes


def bits(n: int) -> int:
    """Fixed width of a value drawn from ``n`` distinct ones."""
    return max(1, math.ceil(math.log2(n))) if n > 1 else 0


class Support(Exact):
    """Propagates which ids are reached (0/1 per id and row) and charges each
    hop's useful bytes to the rows of the batch."""

    def __init__(self, graph: Graph, batch: int):
        super().__init__(graph)
        self.bits = np.zeros(batch, np.float64)
        self._measure_bits: dict = {}

    def _bits_per_edge(self, table: str, src_key: str, measure: str | None) -> int:
        width = bits(self.g.size(self.g.dst_entity(table, src_key)))
        if measure:
            if (table, measure) not in self._measure_bits:
                col = self.g.data.relationships[table].columns[measure]
                self._measure_bits[(table, measure)] = bits(np.unique(col).shape[0])
            width += self._measure_bits[(table, measure)]
        return width

    def hop(self, x, table, src_key, measure=None):
        active = self.mask(x)
        edges = active @ self.g.out_degree(table, src_key).astype(np.float64)
        self.bits += edges * self._bits_per_edge(table, src_key, measure)
        return self.mask(active @ self.g.matrix(table, src_key))

    def scale(self, x, factor):
        return x


def useful_bytes(graph: Graph, schema: str, shape: str, params: dict) -> np.ndarray:
    """Useful bytes of each request of a batch (``params``: one array of B
    values per parameter)."""
    batch = len(next(iter(params.values())))
    ops = Support(graph, batch)
    shapes(schema)[shape](ops, params)
    return ops.bits / 8.0
