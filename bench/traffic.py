"""The one traffic generator. A mix is a data file, ``bench/traffic/<name>.json``:

``loop``
    ``closed``: ``clients`` callers, each sending its next request as soon as
    the previous one is answered. Every caller runs the panels of ``cycle`` in
    that order, callers spread evenly over its positions.
    ``open``: ``round(rate_qps * seconds)`` arrivals at uniform random times
    in the window (a Poisson process given its count), each of a shape drawn
    from ``mix`` (weights) in fixed shares, in a seeded order. The times and
    the order of shapes come from ``arrival_seed`` where the mix gives one:
    every seed then replays one schedule, and the seed draws the data and
    the parameters.
``bucket``
    Every batch is padded to this many rows.
``params``
    How each query parameter is drawn (the SQL's ``:name``), from the data:
    ``{"distinct_of": T, "column": C}`` is a uniform one of the distinct
    values of column C in T. A key ``"a,b"`` draws two values:
    ``{"pair_via": T, "group": G, "column": C}`` takes a uniform distinct
    value ``a`` of C and ``b``, a uniform one of its co-occurrences: column C
    of a row of T, other than ``a``'s own, in a group G that holds ``a``.
    Popular values co-occur more often, so ``b`` is drawn as a drill-down
    from ``a`` would reach it.
A ``distinct_of`` rule, and the first value of ``pair_via``, may add
``"spread_by": [{"table": T, "key": K}, ...]``: the values are ranked by
their size, the number of paths from the value along that chain of
relationships (ties in a seeded order), and the k-th draw of the rule for a
shape takes the rank at quantile frac((k + 1/2) phi), phi the golden ratio.
That is still uniform over the values, but every seed draws the same sizes
in the same turn of each shape, so the work a window holds does not swing
with the seed where a few values (a prolific author, a popular term) cost
far more than the rest. ``pair_via`` may add ``"second_spread_by"`` too:
then the k-th ``b`` is the co-occurrence of ``a`` at quantile
frac((k + 1/2) sqrt 2) when they are ordered by the size of their value
along that chain: still weighted as a uniform co-occurrence is, but the k-th
pair is of the same sizes whatever the seed.
``warmup_batches``
    Batches the loop serves before the window (closed loops serve one cycle).
``check_fraction``
    Share of the window's answers, drawn from the seed, kept and compared
    with the reference once the window has closed.

Requests, arrivals and parameters come from ``--seed`` alone."""
from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

from .data import Dataset

PARAM = re.compile(r":([A-Za-z_]\w*)")
GOLDEN = (5 ** 0.5 - 1) / 2
ROOT2 = 2 ** 0.5 - 1


def sql_params(sql: str) -> list[str]:
    """The named parameters of a query, in order of first use."""
    return list(dict.fromkeys(PARAM.findall(sql)))


@dataclass
class Draw:
    shape: str
    params: dict[str, int]


class Sampler:
    """Draws parameter bindings for the shapes of a deployment."""

    def __init__(self, data: Dataset, spec: dict, shape_params: dict[str, list[str]],
                 seed: int = 0):
        self.data = data
        self.spec = spec
        self.shape_params = shape_params
        self.seed = seed
        self._lookup: dict = {}
        self._ranked: dict = {}
        self._sizes: dict = {}
        self._ties: dict = {}
        self._turn: dict = {}

    def _column(self, table: str, column: str) -> np.ndarray:
        return self.data.relationships[table].columns[column]

    def _index(self, table: str, key: str):
        """Rows of ``table`` by value of ``key``: (row order, start per value,
        the distinct values)."""
        if (table, key) not in self._lookup:
            col = self._column(table, key)
            order = np.argsort(col, kind="stable")
            starts = np.searchsorted(col[order], np.arange(col.max() + 2))
            self._lookup[(table, key)] = (order, starts, np.unique(col))
        return self._lookup[(table, key)]

    def _next_turn(self, key) -> int:
        k = self._turn.get(key, 0)
        self._turn[key] = k + 1
        return k

    def _distinct(self, rng, table: str, column: str, spread_by, k: int) -> int:
        """A value of ``column``; with ``spread_by``, the one of turn ``k``."""
        values = self._index(table, column)[2]
        if not spread_by:
            return int(values[int(rng.integers(0, values.shape[0]))])
        key = (table, column, repr(spread_by))
        if key not in self._ranked:
            self._ranked[key] = self._by_size(values, spread_by, table, column)
        q = ((k + 0.5) * GOLDEN) % 1.0
        ranked = self._ranked[key]
        return int(ranked[int(q * ranked.shape[0])])

    def _size(self, chain: list[dict]) -> np.ndarray:
        """Number of paths along ``chain`` from each id of its first source."""
        key = repr(chain)
        if key not in self._sizes:
            size = None
            for hop in reversed(chain):  # paths onward from each id of a hop's source
                rel = self.data.relationships[hop["table"]]
                src, dst = rel.columns[hop["key"]], rel.columns[rel.other(hop["key"])]
                w = np.ones(src.shape[0]) if size is None else size[dst]
                size = np.bincount(src, weights=w,
                                   minlength=self.data.sizes[rel.entity_of(hop["key"])])
            self._sizes[key] = size
        return self._sizes[key]

    def _tie_order(self, n: int) -> np.ndarray:
        """A rank for each of ``n`` ids, drawn from the seed, that breaks ties
        in size."""
        if n not in self._ties:
            self._ties[n] = np.random.default_rng([self.seed, 6]).permutation(n)
        return self._ties[n]

    def _by_size(self, values, chain: list[dict], table: str, column: str) -> np.ndarray:
        """``values`` (ids of ``column`` of ``table``) sorted by their number
        of paths along ``chain``, ties in an order drawn from the seed."""
        n = self.data.sizes[self.data.relationships[table].entity_of(column)]
        return values[np.lexsort((self._tie_order(n)[values], self._size(chain)[values]))]

    def _pair_via(self, rng, rule: dict, shape: str, names: str) -> tuple[int, int]:
        table, group, column = rule["pair_via"], rule["group"], rule["column"]
        second = rule.get("second_spread_by")
        while True:
            k = self._next_turn((shape, names))
            a = self._distinct(rng, table, column, rule.get("spread_by"), k)
            others = self._co_values(table, group, column, a)
            if not others.shape[0]:
                continue
            if not second:
                return a, int(others[int(rng.integers(0, others.shape[0]))])
            n = self.data.sizes[self.data.relationships[table].entity_of(column)]
            key = self._size(second).astype(np.int64)[others] * n + self._tie_order(n)[others]
            kth = int(((k + 0.5) * ROOT2) % 1.0 * others.shape[0])
            return a, int(others[np.argpartition(key, kth)[kth]])

    def _co_values(self, table: str, group: str, column: str, a: int) -> np.ndarray:
        """Column ``column`` of every row of ``table`` whose ``group`` holds
        ``a`` somewhere, ``a`` itself left out: one entry per co-occurrence."""
        g, c = self._column(table, group), self._column(table, column)
        corder, cstarts, _ = self._index(table, column)
        gorder, gstarts, _ = self._index(table, group)
        groups = g[corder[cstarts[a]:cstarts[a + 1]]]
        lo, n = gstarts[groups], gstarts[groups + 1] - gstarts[groups]
        rows = gorder[np.repeat(lo - np.cumsum(n) + n, n) + np.arange(n.sum())]
        vals = c[rows]
        return vals[vals != a]

    def draw(self, rng: np.random.Generator, shape: str) -> Draw:
        want = self.shape_params[shape]
        out: dict[str, int] = {}
        for names, rule in self.spec.items():
            names_l = names.split(",")
            if not set(names_l) & set(want):
                continue
            if "pair_via" in rule:
                out.update(zip(names_l, self._pair_via(rng, rule, shape, names)))
            else:
                out[names_l[0]] = self._distinct(rng, rule["distinct_of"], rule["column"],
                                                 rule.get("spread_by"),
                                                 self._next_turn((shape, names)))
        missing = [n for n in want if n not in out]
        if missing:
            raise ValueError(f"traffic draws no value for {missing} of {shape}")
        return Draw(shape, {n: out[n] for n in want})


def shapes_of(traffic: dict) -> list[str]:
    return list(traffic["cycle"] if traffic["loop"] == "closed" else traffic["mix"])


class ClosedClients:
    """The request streams of a closed loop: client ``c``'s k-th request."""

    def __init__(self, traffic: dict, sampler: Sampler, seed: int):
        self.cycle = traffic["cycle"]
        self.n = traffic["clients"]
        self.sampler = sampler
        self.rngs = [np.random.default_rng([seed, 1, c]) for c in range(self.n)]
        self.k = [0] * self.n

    def next(self, client: int) -> Draw:
        pos = client * len(self.cycle) // self.n + self.k[client]
        self.k[client] += 1
        return self.sampler.draw(self.rngs[client], self.cycle[pos % len(self.cycle)])


def open_arrivals(traffic: dict, sampler: Sampler, seed: int,
                  seconds: float) -> list[tuple[float, Draw]]:
    """Arrival offsets (seconds from the window's start) with their requests."""
    rng = np.random.default_rng([traffic.get("arrival_seed", seed), 2])
    n = int(round(traffic["rate_qps"] * seconds))
    times = np.sort(rng.uniform(0.0, seconds, n))
    names = list(traffic["mix"])
    w = np.asarray([traffic["mix"][s] for s in names], np.float64)
    counts = np.floor(n * w / w.sum()).astype(int)
    counts[: n - counts.sum()] += 1
    shapes = rng.permutation(np.repeat(np.arange(len(names)), counts))
    rng = np.random.default_rng([seed, 7])
    return [(float(t), sampler.draw(rng, names[s])) for t, s in zip(times, shapes)]


def warmup_draws(traffic: dict, sampler: Sampler, seed: int) -> list[Draw]:
    rng = np.random.default_rng([seed, 3])
    names = shapes_of(traffic)
    n = traffic.get("warmup_batches", len(names)) * traffic["bucket"]
    return [sampler.draw(rng, names[i % len(names)]) for i in range(n)]
