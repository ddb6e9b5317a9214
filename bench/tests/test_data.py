"""The generator copies under ``bench/data`` give the arrays the program's
generator (``repro.data.synth_graph``) gave when the benchmark was made."""
import json

import numpy as np
import pytest

from bench.data import generate
from bench.tests.common import ROOT


def _pubmed_cfg(n: int) -> dict:
    cfg = json.load(open(ROOT / "bench/configs/pubmed-m-1m.json"))
    pub = cfg["published"]
    cfg.update(n_docs=n,
               n_authors=max(1, round(n * pub["n_authors"] / pub["n_docs"])),
               dt_rows=int(n * (pub["dt_rows"] / pub["n_docs"])),
               da_rows=int(n * (pub["da_rows"] / pub["n_docs"])))
    return cfg


def test_pubmed_config_counts_follow_table1_ratios():
    cfg = json.load(open(ROOT / "bench/configs/pubmed-m-1m.json"))
    want = _pubmed_cfg(cfg["n_docs"])
    for k in ("n_authors", "dt_rows", "da_rows", "n_terms"):
        assert cfg[k] == want[k], k


@pytest.mark.parametrize("n,seed", [(2000, 0), (5000, 7), (3000, 2**31 + 5)])
def test_pubmed_copy_matches_program(n, seed):
    from repro.data.synth_graph import pubmed_table1_scale

    want = pubmed_table1_scale(n, seed=seed)
    got = generate("pubmed", _pubmed_cfg(n), seed)
    assert got.sizes == {e: t.size for e, t in want.entities.items()}
    np.testing.assert_array_equal(got.attributes["Document"]["Year"],
                                  want.entities["Document"].attributes["Year"])
    for name, rel in want.relationships.items():
        for col, arr in rel.columns.items():
            np.testing.assert_array_equal(got.relationships[name].columns[col], arr)
