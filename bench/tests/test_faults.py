"""A whole run, with the look for a chip skipped, at a small size on the
CPU: sound, it comes out correct; with the timed path broken underneath it
comes out not correct, once for each fault a cell can have: an answer
altered where it is produced, and half of each batch left out. (The cells
run on one chip: no exchange between chips to leave out.)"""
import jax
import pytest

from bench import run as R
from bench.sut import System


def broken(fault):
    class Broken(System):
        """The program with its batched executables broken underneath the
        runner, before any warm-up call."""

        def __init__(self, data, queries):
            super().__init__(data, queries)
            for pq in self.prepared.values():
                pq.batched_fn = self._wrap(pq.batched_fn)

        @staticmethod
        def _wrap(fn):
            def run(*args):
                out = fn(*args)
                if fault == "altered":
                    return out.at[0, out[0].argmax()].multiply(2.0)
                return out.at[out.shape[0] // 2:].set(0.0)
            return run

    return Broken


@pytest.mark.parametrize("workload", ["pubmed-m-1m.dashboard", "pubmed-m-1m.term-pairs"])
@pytest.mark.parametrize("fault", [None, "altered", "half_batch"])
def test_run_is_correct_only_when_sound(tiny_cell, workload, fault):
    cell = tiny_cell(workload)
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_qps"] = 400  # above capacity: every batch is full
        cell.traffic["check_fraction"] = 1.0
    out = R.run_cell(cell, 2**32 + 17, 2.0, False, jax.devices()[:1],
                     {"hbm_bytes_per_s": 819e9},
                     system_cls=broken(fault) if fault else System)
    assert out["attempted"] > 0
    assert out["correct"] is (fault is None), out["check"]
    assert list(out)[-1] == "check"
