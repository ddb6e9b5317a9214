"""The plain reference: each query shape of a deployment written out as the
hops its SQL names, over :class:`bench.graph.Graph`, in float64.

A shape is a function ``shape(ops, params) -> [B, n_group]`` in
``bench/reference/<schema>.py`` (``SHAPES``); ``params`` holds one array of
B values per parameter. Frontiers are sparse ``[B, n]`` matrices, so a hop
costs what its active sources' edges cost. ``ops`` decides the arithmetic:
:class:`Exact` is the reference; :class:`Bfloat16` is the control, the same
reference rounded to bfloat16 after every step; ``bench.bytes.Support``
propagates only which ids are reached, to count useful bytes. Every weight a
shape multiplies by is positive, so support is the same in all three."""
from __future__ import annotations

import importlib

import ml_dtypes
import numpy as np
import scipy.sparse as sp

from ..graph import Graph


def shapes(schema: str) -> dict:
    return importlib.import_module(f"bench.reference.{schema}").SHAPES


class Exact:
    """Float64 arithmetic: the answers every run is compared with."""

    def __init__(self, graph: Graph):
        self.g = graph

    def round(self, v: np.ndarray) -> np.ndarray:
        return v

    def _rounded(self, x: sp.csr_matrix) -> sp.csr_matrix:
        x = x.tocsr()
        x.data = self.round(x.data)
        return x

    def attr(self, entity: str, name: str) -> np.ndarray:
        return self.g.attr(entity, name)

    def seed(self, entity: str, ids) -> sp.csr_matrix:
        """One row per binding, 1 at its id."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        b = ids.shape[0]
        return sp.csr_matrix((np.ones(b), (np.arange(b), ids)),
                             shape=(b, self.g.size(entity)))

    def matrix(self, table: str, src_key: str, measure: str | None):
        return self.g.matrix(table, src_key, measure)

    def hop(self, x, table: str, src_key: str, measure: str | None = None):
        """``x`` carried one hop over ``table`` from ``src_key``, each path
        weighted by ``measure`` (or 1), paths to one id summed."""
        return self._rounded(x @ self.matrix(table, src_key, measure))

    def scale(self, x, factor):
        """Each entry (row, id) times ``factor(rows, ids)``."""
        x = x.tocoo()
        f = self.round(np.asarray(factor(x.row, x.col), np.float64))
        return self._rounded(sp.csr_matrix(
            (self.round(x.data) * f, (x.row, x.col)), shape=x.shape))

    def mask(self, x):
        """1 where ``x`` reaches an id, else 0."""
        m = (x > 0).astype(np.float64)
        m.eliminate_zeros()
        return m

    def intersect(self, a, b):
        return a.multiply(b).tocsr()

    def dense(self, x) -> np.ndarray:
        return np.asarray(x.toarray(), np.float64)


class Bfloat16(Exact):
    """The control: frontiers, measures, factors and every hop's result held
    in bfloat16 (products summed in float64, then rounded), the step below
    the float32 the configuration states."""

    def __init__(self, graph: Graph):
        super().__init__(graph)
        self._mats: dict = {}

    def round(self, v: np.ndarray) -> np.ndarray:
        return np.asarray(v).astype(ml_dtypes.bfloat16).astype(np.float64)

    def matrix(self, table: str, src_key: str, measure: str | None):
        k = (table, src_key, measure)
        if k not in self._mats:
            a = self.g.matrix(table, src_key, measure).copy()
            a.data = self.round(a.data)
            self._mats[k] = a
        return self._mats[k]
