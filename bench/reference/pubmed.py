"""The PubMed-M dashboard queries (GQ-Fast, arXiv:1602.00033 §4) as hops.
Each matches the SQL under the same name in ``bench/configs/pubmed-*.json``;
``bench/tests/test_reference.py`` compares them with the program's own
numpy engine at a small size."""
from __future__ import annotations

import numpy as np


def SD(ops, p):
    """Documents sharing terms with d0: COUNT(*) over dt1.Term = dt2.Term."""
    x = ops.seed("Document", p["d0"])
    x = ops.hop(x, "DT", "Doc")
    return ops.dense(ops.hop(x, "DT", "Term"))


def FSD(ops, p):
    """SUM(dt1.Fre * dt2.Fre) / (|d1.Year - d2.Year| + 1) per document d2."""
    year = ops.attr("Document", "Year").astype(np.float64)
    d0 = np.asarray(p["d0"], np.int64).reshape(-1)
    x = ops.seed("Document", d0)
    x = ops.hop(x, "DT", "Doc", measure="Fre")
    x = ops.hop(x, "DT", "Term", measure="Fre")
    x = ops.scale(x, lambda rows, ids: 1.0 / (np.abs(year[d0[rows]] - year[ids]) + 1.0))
    return ops.dense(x)


def AS(ops, p):
    """Authors similar to a0: SUM(dt1.Fre * dt2.Fre) / (2017 - d.Year) over
    a0's documents → their terms → documents with the term → their authors."""
    year = ops.attr("Document", "Year").astype(np.float64)
    x = ops.seed("Author", p["a0"])
    x = ops.hop(x, "DA", "Author")
    x = ops.hop(x, "DT", "Doc", measure="Fre")
    x = ops.hop(x, "DT", "Term", measure="Fre")
    x = ops.scale(x, lambda rows, ids: 1.0 / (2017.0 - year[ids]))
    return ops.dense(ops.hop(x, "DA", "Doc"))


def _docs_with_both(ops, p):
    m1 = ops.mask(ops.hop(ops.seed("Term", p["t1"]), "DT", "Term"))
    m2 = ops.mask(ops.hop(ops.seed("Term", p["t2"]), "DT", "Term"))
    return ops.intersect(m1, m2)


def AD(ops, p):
    """Authors of the documents holding both t1 and t2: COUNT(*)."""
    return ops.dense(ops.hop(_docs_with_both(ops, p), "DA", "Doc"))


def FAD(ops, p):
    """Terms of the documents holding both t1 and t2: SUM(dt2.Fre)."""
    return ops.dense(ops.hop(_docs_with_both(ops, p), "DT", "Doc", measure="Fre"))


SHAPES = {"SD": SD, "FSD": FSD, "AS": AS, "AD": AD, "FAD": FAD}
