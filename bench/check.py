"""The comparison that decides ``correct``.

Every kept answer of the window is recomputed by the plain reference
(``bench/reference``, float64) once the window has closed, and three numbers
are compared with the limits the configuration states (``limits``):

``failed``
    Requests of the window that ended in an error or were never answered.
``int_mismatch``
    Entries, over the answers of integer-valued shapes (COUNT, and SUM of an
    integer measure), that differ from the reference at all. float32 holds
    these sums exactly, so the limit is 0.
``max_rel_err``
    The widest relative gap |got - want| / |want| over the entries of the
    real-valued shapes (a reference 0 takes |got| itself).

A number that no kept answer bears on is not reported."""
from __future__ import annotations

import numpy as np

from .graph import Graph
from .reference import Exact, shapes

CHUNK = 16


def reference_answers(graph: Graph, schema: str, shape: str,
                      params: list[dict[str, int]], ops=None) -> np.ndarray:
    """``[len(params), n_group]`` answers of one shape, in chunks of rows
    (``ops``: the arithmetic, float64 :class:`Exact` unless given)."""
    fn, ops = shapes(schema)[shape], ops or Exact(graph)
    out = []
    for i in range(0, len(params), CHUNK):
        part = params[i:i + CHUNK]
        out.append(fn(ops, {k: np.asarray([p[k] for p in part]) for k in part[0]}))
    return np.concatenate(out)


class Comparison:
    """Accumulates the numbers over chunks of answers."""

    def __init__(self):
        self.numbers: dict[str, float] = {}

    def add(self, answer: str, got: np.ndarray, want: np.ndarray) -> None:
        got = np.asarray(got, np.float64)
        if got.shape != want.shape:
            raise ValueError(f"answer shape {got.shape} != reference {want.shape}")
        if answer == "integer":
            n = int(np.count_nonzero(got != want))
            self.numbers["int_mismatch"] = self.numbers.get("int_mismatch", 0) + n
        else:
            den = np.where(want != 0, np.abs(want), 1.0)
            err = float(np.max(np.abs(got - want) / den, initial=0.0))
            self.numbers["max_rel_err"] = max(self.numbers.get("max_rel_err", 0.0), err)


def compare_requests(graph: Graph, schema: str, queries: dict, kept: list,
                     answers=None) -> dict[str, float]:
    """Numbers for the kept requests (each with ``shape``, ``params`` and
    ``value``); ``answers`` replaces the values (the control)."""
    cmp = Comparison()
    by_shape: dict[str, list[int]] = {}
    for i, r in enumerate(kept):
        by_shape.setdefault(r.shape, []).append(i)
    for shape, idx in by_shape.items():
        want = reference_answers(graph, schema, shape, [kept[i].params for i in idx])
        if answers is not None:
            got = answers(shape, [kept[i].params for i in idx])
        else:
            got = np.stack([np.asarray(kept[i].value) for i in idx])
        cmp.add(queries[shape]["answer"], got, want)
    return cmp.numbers


def judge(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``; every number must be within
    its limit, and a number without a limit fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        out[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and value <= limit
    return ok, out
