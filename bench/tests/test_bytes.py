"""``bench/bytes.py`` against a brute-force count on a tiny graph: every
hop's active sources found by walking edge lists in Python, every edge that
leaves one charged its packed bits."""
import math

import numpy as np
import pytest

from bench.bytes import bits, useful_bytes
from bench.data import Dataset, Relationship
from bench.graph import Graph


def _tiny() -> Dataset:
    rng = np.random.default_rng(4)

    def pairs(n_a, n_b, k):
        keys = np.unique(rng.integers(0, n_a * n_b, k))
        return keys // n_b, keys % n_b

    dt_doc, dt_term = pairs(40, 12, 150)
    da_doc, da_author = pairs(40, 9, 70)
    return Dataset(
        sizes={"Document": 40, "Term": 12, "Author": 9},
        attributes={"Document": {"Year": rng.integers(1990, 2016, 40)}},
        relationships={
            "DT": Relationship(("Doc", "Term"), ("Document", "Term"),
                               {"Doc": dt_doc, "Term": dt_term,
                                "Fre": 1 + rng.integers(0, 5, dt_doc.shape[0])}),
            "DA": Relationship(("Doc", "Author"), ("Document", "Author"),
                               {"Doc": da_doc, "Author": da_author}),
        },
    )


def _walk(data, table, src_key, active, measure=None):
    """(reached ids, bits charged) of one hop, by looping over the rows."""
    rel = data.relationships[table]
    dst_key = rel.other(src_key)
    n_dst = data.sizes[rel.entity_of(dst_key)]
    width = math.ceil(math.log2(n_dst))
    if measure:
        width += math.ceil(math.log2(len(set(rel.columns[measure].tolist()))))
    reached, charged = set(), 0
    for s, d in zip(rel.columns[src_key].tolist(), rel.columns[dst_key].tolist()):
        if s in active:
            reached.add(d)
            charged += width
    return reached, charged


def _chain(data, start, hops):
    active, total = start, 0
    for table, key, measure in hops:
        active, b = _walk(data, table, key, active, measure)
        total += b
    return active, total


BRUTE = {
    "SD": lambda d, p: _chain(d, {p["d0"]}, [("DT", "Doc", None), ("DT", "Term", None)])[1],
    "FSD": lambda d, p: _chain(d, {p["d0"]}, [("DT", "Doc", "Fre"), ("DT", "Term", "Fre")])[1],
    "AS": lambda d, p: _chain(d, {p["a0"]}, [("DA", "Author", None), ("DT", "Doc", "Fre"),
                                             ("DT", "Term", "Fre"), ("DA", "Doc", None)])[1],
    "AD": "mask", "FAD": "mask",
}


def _mask_shape(d, p, last):
    m1, b1 = _walk(d, "DT", "Term", {p["t1"]})
    m2, b2 = _walk(d, "DT", "Term", {p["t2"]})
    table, key, measure = last
    return b1 + b2 + _walk(d, table, key, m1 & m2, measure)[1]


@pytest.mark.parametrize("shape", ["SD", "FSD", "AS", "AD", "FAD"])
def test_useful_bytes_match_brute_force(shape):
    data = _tiny()
    graph = Graph(data)
    rng = np.random.default_rng(1)
    names = {"SD": ["d0"], "FSD": ["d0"], "AS": ["a0"], "AD": ["t1", "t2"],
             "FAD": ["t1", "t2"]}[shape]
    dom = {"d0": 40, "a0": 9, "t1": 12, "t2": 12}
    params = [{n: int(rng.integers(0, dom[n])) for n in names} for _ in range(8)]
    got = useful_bytes(graph, "pubmed", shape,
                       {n: np.asarray([p[n] for p in params]) for n in names})
    for p, g in zip(params, got):
        if BRUTE[shape] == "mask":
            last = ("DA", "Doc", None) if shape == "AD" else ("DT", "Doc", "Fre")
            want = _mask_shape(data, p, last)
        else:
            want = BRUTE[shape](data, p)
        assert g == pytest.approx(want / 8.0), (shape, p)
    assert got.sum() > 0


def test_bits():
    assert [bits(n) for n in (1, 2, 3, 50, 27883, 1_000_000)] == [0, 1, 2, 6, 15, 20]
