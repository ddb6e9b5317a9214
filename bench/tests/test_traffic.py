"""The traffic generator: each parameter rule draws values the data holds,
the same seed gives the same requests, and an open loop's arrival count is
fixed by its rate."""
import numpy as np
import pytest

from bench.data import generate
from bench.traffic import (
    ClosedClients, Sampler, open_arrivals, sql_params, warmup_draws,
)
from bench.tests.common import TINY


@pytest.fixture(scope="module")
def pubmed():
    import json

    from bench.tests.common import ROOT

    cfg = json.load(open(ROOT / "bench/configs/pubmed-m-1m.json"))
    cfg.update(TINY["pubmed"])
    return generate("pubmed", cfg, 5)


def test_sql_params():
    assert sql_params("SELECT x WHERE a = :t1 AND b = :t2 OR c = :t1") == ["t1", "t2"]


def test_rules_draw_values_the_data_holds(pubmed):
    dt, da = pubmed.relationships["DT"].columns, pubmed.relationships["DA"].columns
    spec = {
        "a0": {"distinct_of": "DA", "column": "Author"},
        "d0": {"distinct_of": "DT", "column": "Doc"},
        "t1,t2": {"pair_via": "DT", "group": "Doc", "column": "Term"},
    }
    s = Sampler(pubmed, spec, {"A": ["a0"], "D": ["d0"], "T": ["t1", "t2"]})
    rng = np.random.default_rng(0)
    pairs = set(zip(dt["Doc"].tolist(), dt["Term"].tolist()))
    docs_of = {}
    for d, t in pairs:
        docs_of.setdefault(t, set()).add(d)
    for _ in range(50):
        assert s.draw(rng, "A").params["a0"] in set(da["Author"].tolist())
        assert s.draw(rng, "D").params["d0"] in set(dt["Doc"].tolist())
        p = s.draw(rng, "T").params
        assert p["t1"] != p["t2"]
        assert docs_of[p["t1"]] & docs_of[p["t2"]], "the pair co-occurs in a document"
    with pytest.raises(ValueError):
        Sampler(pubmed, {}, {"A": ["a0"]}).draw(rng, "A")


def test_distinct_of_is_uniform_over_values(pubmed):
    s = Sampler(pubmed, {"t": {"distinct_of": "DT", "column": "Term"}}, {"X": ["t"]})
    rng = np.random.default_rng(1)
    got = [s.draw(rng, "X").params["t"] for _ in range(4000)]
    # Zipf-popular terms are not favoured: the top term is drawn about 1/n_terms
    top = np.bincount(pubmed.relationships["DT"].columns["Term"]).argmax()
    assert got.count(int(top)) < 40


def test_closed_clients_cycle_the_panels_from_spread_positions(pubmed):
    traffic = {"cycle": ["SD", "AS"], "clients": 4}
    s = Sampler(pubmed, {"d0": {"distinct_of": "DT", "column": "Doc"},
                         "a0": {"distinct_of": "DA", "column": "Author"}},
                {"SD": ["d0"], "AS": ["a0"]})
    c = ClosedClients(traffic, s, 9)
    first = [c.next(i).shape for i in range(4)]
    assert first == ["SD", "SD", "AS", "AS"]
    assert [c.next(0).shape for _ in range(3)] == ["AS", "SD", "AS"]
    again = ClosedClients(traffic, s, 9)
    assert [again.next(i).params for i in range(4)] == \
        [ClosedClients(traffic, s, 9).next(i).params for i in range(4)]


def test_open_arrivals_fixed_count_sorted_and_seeded(pubmed):
    s = Sampler(pubmed, {"d0": {"distinct_of": "DT", "column": "Doc"},
                         "a0": {"distinct_of": "DA", "column": "Author"}},
                {"SD": ["d0"], "AS": ["a0"]})
    traffic = {"loop": "open", "rate_qps": 30, "mix": {"SD": 2, "AS": 1},
               "bucket": 8, "warmup_batches": 2}
    a = open_arrivals(traffic, s, 2**33, 10.0)
    b = open_arrivals(traffic, s, 2**33, 10.0)
    assert len(a) == 300
    t = [x for x, _ in a]
    assert t == sorted(t) and 0 <= t[0] and t[-1] < 10.0
    assert [d.params for _, d in a] == [d.params for _, d in b]
    assert sum(d.shape == "SD" for _, d in a) == 200
    assert len(warmup_draws(traffic, s, 1)) == 16
    # with an arrival_seed every seed replays one schedule; the seed draws the parameters
    fixed = dict(traffic, arrival_seed=3)
    c = open_arrivals(fixed, s, 2**33, 10.0)
    d = open_arrivals(fixed, s, 2**33 + 1, 10.0)
    assert [(t, x.shape) for t, x in c] == [(t, x.shape) for t, x in d]
    assert [x.params for _, x in c] != [x.params for _, x in d]


def test_spread_by_draws_the_same_sizes_for_every_seed(pubmed):
    """Stratified draws: uniform over the values, and the k-th draw has the
    same size (paths along the chain) whatever the seed."""
    spec = {"a0": {"distinct_of": "DA", "column": "Author",
                   "spread_by": [{"table": "DA", "key": "Author"},
                                 {"table": "DT", "key": "Doc"}]}}
    da, dt = pubmed.relationships["DA"].columns, pubmed.relationships["DT"].columns
    terms_per_doc = np.bincount(dt["Doc"], minlength=pubmed.sizes["Document"])
    size = np.bincount(da["Author"], weights=terms_per_doc[da["Doc"]],
                       minlength=pubmed.sizes["Author"])
    runs = []
    for seed in (1, 2):
        s = Sampler(pubmed, spec, {"AS": ["a0"]}, seed)
        rng = np.random.default_rng(seed)
        runs.append([s.draw(rng, "AS").params["a0"] for _ in range(300)])
    assert [size[a] for a in runs[0]] == [size[a] for a in runs[1]]
    assert runs[0] != runs[1]
    # uniform over the authors present: the draws' sizes follow their quantiles
    present = np.unique(da["Author"])
    drawn = np.sort(size[runs[0]])
    want = np.sort(size[present])[((np.arange(300) + 0.5) / 300 * present.shape[0]).astype(int)]
    assert np.abs(np.searchsorted(np.sort(size[present]), drawn)
                  - np.searchsorted(np.sort(size[present]), want)).max() < 0.05 * present.shape[0]


def test_second_spread_by_stratifies_the_pair_and_turns_are_per_shape(pubmed):
    """The k-th pair of a shape takes its second value at a fixed quantile,
    by size, of the first value's co-occurrences; each shape counts its own
    turns, so interleaving shapes leaves each shape's draws as they were."""
    dt = pubmed.relationships["DT"].columns
    spec = {"t1,t2": {"pair_via": "DT", "group": "Doc", "column": "Term",
                      "spread_by": [{"table": "DT", "key": "Term"}],
                      "second_spread_by": [{"table": "DT", "key": "Term"}]}}
    shapes = {"AD": ["t1", "t2"], "FAD": ["t1", "t2"]}
    size = np.bincount(dt["Term"], minlength=pubmed.sizes["Term"])
    docs_of = {}
    for d, t in zip(dt["Doc"].tolist(), dt["Term"].tolist()):
        docs_of.setdefault(t, set()).add(d)
    alone = Sampler(pubmed, spec, shapes, 3)
    mixed = Sampler(pubmed, spec, shapes, 3)
    rng_a, rng_m = np.random.default_rng(4), np.random.default_rng(4)
    ad = [alone.draw(rng_a, "AD").params for _ in range(60)]
    got = []
    for _ in range(60):
        got.append(mixed.draw(rng_m, "AD").params)
        mixed.draw(rng_m, "FAD")
    assert ad == got
    qs = []
    for k, p in enumerate(ad):
        assert docs_of[p["t1"]] & docs_of[p["t2"]], "the pair co-occurs in a document"
        q = ((k + 0.5) * (2 ** 0.5 - 1)) % 1.0
        qs.append(q)
        if q > 0.9:  # a high quantile draws a popular co-occurring term
            assert size[p["t2"]] > np.median(size[size > 0])
    assert min(qs) < 0.1 and max(qs) > 0.9
