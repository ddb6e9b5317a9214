"""Each relationship table of a :class:`bench.data.Dataset` as sparse
matrices, one per direction, built with scipy and nothing of the program.
The plain reference and the useful-bytes count both walk them."""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from .data import Dataset


class Graph:
    def __init__(self, data: Dataset):
        self.data = data
        self._mats: dict = {}

    def size(self, entity: str) -> int:
        return self.data.sizes[entity]

    def attr(self, entity: str, name: str) -> np.ndarray:
        return self.data.attributes[entity][name]

    def matrix(self, table: str, src_key: str, measure: str | None = None):
        """``[n_src, n_dst]`` CSR whose entry (s, d) sums ``measure`` (or 1)
        over the rows of ``table`` with ``src_key`` = s and the other key = d;
        ``x @ A`` carries a ``[B, n_src]`` frontier one hop."""
        k = (table, src_key, measure)
        if k not in self._mats:
            rel = self.data.relationships[table]
            order, indices, indptr, shape = self._structure(table, src_key)
            vals = (rel.columns[measure][order].astype(np.float64) if measure
                    else np.ones(order.shape[0]))
            self._mats[k] = sp.csr_matrix((vals, indices, indptr), shape=shape)
        return self._mats[k]

    def _structure(self, table: str, src_key: str):
        """Rows of ``table`` sorted by (source, destination): the order, the
        destinations and the CSR offsets (the pairs of a table are distinct)."""
        k = (table, src_key, "#csr")
        if k not in self._mats:
            rel = self.data.relationships[table]
            dst_key = rel.other(src_key)
            src = rel.columns[src_key].astype(np.int64)
            dst = rel.columns[dst_key].astype(np.int64)
            n_src = self.size(rel.entity_of(src_key))
            n_dst = self.size(rel.entity_of(dst_key))
            order = np.argsort(src * n_dst + dst)
            indptr = np.zeros(n_src + 1, np.int64)
            np.cumsum(np.bincount(src, minlength=n_src), out=indptr[1:])
            self._mats[k] = (order, dst[order], indptr, (n_src, n_dst))
        return self._mats[k]

    def out_degree(self, table: str, src_key: str) -> np.ndarray:
        """Rows of ``table`` per value of ``src_key``."""
        return np.diff(self._structure(table, src_key)[2])

    def dst_entity(self, table: str, src_key: str) -> str:
        rel = self.data.relationships[table]
        return rel.entity_of(rel.other(src_key))
