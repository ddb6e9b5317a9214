"""PubMed-M data, copied from ``repro.data.synth_graph`` (``make_pubmed`` with
``exact_counts=True``, as ``pubmed_table1_scale`` calls it) so that a change
to the program's generator cannot change the benchmark's data. The same
configuration and seed give the same arrays as the original
(``bench/tests/test_data.py``).

DT(Doc, Term, Fre) and DA(Doc, Author) hold exactly ``dt_rows`` and
``da_rows`` distinct pairs: documents uniform, terms Zipf ``zipf_term``,
authors Zipf ``zipf_author``; Fre is 1 + Zipf ``fre_zipf`` over
``fre_max`` values; Document.Year is uniform over [year_min, year_max]."""
from __future__ import annotations

import numpy as np

from . import Dataset, Relationship


def _zipf_choice(rng: np.random.Generator, n: int, size: int, s: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-s)
    p /= p.sum()
    return rng.choice(n, size=size, p=p)


def _dedupe_pairs(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    m = b.max() + 1
    key = np.unique(a.astype(np.int64) * m + b)
    return (key // m).astype(a.dtype), (key % m).astype(b.dtype)


def _distinct_pairs(rng, n_docs: int, n_other: int, target: int, s: float):
    a = b = np.zeros(0, np.int64)
    while a.shape[0] < target:
        n = int((target - a.shape[0]) * 1.4) + 16
        a, b = _dedupe_pairs(
            np.concatenate([a, rng.integers(0, n_docs, size=n)]),
            np.concatenate([b, _zipf_choice(rng, n_other, n, s)]),
        )
    keep = np.sort(rng.choice(a.shape[0], target, replace=False))
    return a[keep], b[keep]


def generate(cfg: dict, seed: int) -> Dataset:
    rng = np.random.default_rng(seed)
    n_docs, n_terms, n_authors = cfg["n_docs"], cfg["n_terms"], cfg["n_authors"]
    dt_doc, dt_term = _distinct_pairs(rng, n_docs, n_terms, cfg["dt_rows"],
                                      cfg["zipf_term"])
    fre = 1 + _zipf_choice(rng, cfg["fre_max"], dt_doc.shape[0], cfg["fre_zipf"])
    da_doc, da_author = _distinct_pairs(rng, n_docs, n_authors, cfg["da_rows"],
                                        cfg["zipf_author"])
    year = rng.integers(cfg["year_min"], cfg["year_max"] + 1, size=n_docs)
    return Dataset(
        sizes={"Document": n_docs, "Term": n_terms, "Author": n_authors},
        attributes={"Document": {"Year": year}},
        relationships={
            "DT": Relationship(("Doc", "Term"), ("Document", "Term"),
                               {"Doc": dt_doc, "Term": dt_term, "Fre": fre}),
            "DA": Relationship(("Doc", "Author"), ("Document", "Author"),
                               {"Doc": da_doc, "Author": da_author}),
        },
    )
