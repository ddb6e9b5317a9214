"""Self-tests of the benchmark: ``python -m pytest bench/tests``.

They run on the CPU at small sizes (the program's kernels in Pallas
interpret mode) and never need a chip."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

@pytest.fixture
def tiny_cell():
    """``tiny_cell(workload)``: the cell as BENCHMARK.json has it, its
    deployment cut to a tiny size."""
    from bench import spec as S
    from bench.tests.common import TINY

    def make(workload: str):
        cell = S.cell(ROOT, workload)
        cell.config.update(TINY[cell.config["schema"]])
        return cell

    return make
