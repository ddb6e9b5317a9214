"""The reduction of what the program puts into a trace
(``bench/program_trace.py``), on a small trace recorded on a v5e chip with
the program's spans and hop labels (``data/dashboard-program.xplane.pb.gz``,
made by ``record_trace.py``: one cycle of the dashboard's five shapes at
20,000 documents); and the accepted per-layer readers, pinned on the older
fixture (``data/dashboard.xplane.pb.gz``), which holds none of that."""
import json
import math
import re
from types import SimpleNamespace

import pytest

from bench import program_trace as P
from bench import trace as T
from bench.tests.common import ROOT

FIXTURE = ROOT / "bench/tests/data/dashboard-program.xplane.pb.gz"
OLD = ROOT / "bench/tests/data/dashboard.xplane.pb.gz"
KERNELS = re.compile(r"gqfast_(hop|fused_hops)(\.\d+)?$")
CONFIG = ROOT / "bench/configs/pubmed-m-1m.json"


@pytest.fixture(scope="module")
def pt():
    return P.load(str(FIXTURE))


@pytest.fixture(scope="module")
def shapes():
    with open(CONFIG) as f:
        return P.shape_names(json.load(f))


def test_every_hop_kernel_carries_a_label(pt):
    assert pt.kernels
    for op, label, *_ in pt.kernels:
        assert KERNELS.match(op)
        parts = label.split("+")
        assert all(re.fullmatch(r"h\d+:\w+\.\w+->\w+:(pull|src|fused)", p)
                   for p in parts), label
        assert (len(parts) == 2) == op.startswith("gqfast_fused_hops")


def test_device_op_names_are_unchanged(pt):
    tr = T.load(str(FIXTURE))
    lo, hi = tr.window()
    names = [n for n, _ in T.top_ops(tr, lo, hi, 50) if n.startswith("gqfast_")]
    assert names and all(KERNELS.match(n) for n in names)


def test_every_module_run_is_linked_and_the_clocks_agree(pt):
    lo, hi = pt.window()
    clk = P.clock(pt, lo, hi)
    assert clk["modules"] >= 5 and clk["linked"] == clk["modules"]
    assert clk["consistent"]
    assert clk["worst_ms"] < 1.0 and abs(clk["offset_ms"]) < 1.0


def test_hop_time_is_attributed_to_shape_and_hop(pt, shapes):
    b = P.breakdown(pt, shapes, top=100)
    assert b["hops_attributed"] >= 0.99
    assert b["compiles_in_window"] == 0
    assert {name.split("/")[0] for name, *_ in b["hops"]} == set(
        json.load(open(CONFIG))["queries"])
    for name, device_s, rows, per_row in b["hops"]:
        assert device_s > 0 and rows > 0 and rows % 32 == 0, name
        assert per_row is None or math.isfinite(per_row)
    assert [x for *_, x in b["hops"] if x is not None]
    gaps = b["idle_gaps_program"]
    assert gaps and all(g > 0 for _, g in gaps)
    assert all(n.startswith(("repro.", "bench.")) or n == "idle" for n, _ in gaps)


def _spans(events):
    """The fixture's ``repro.*`` events as the program's span tree, in the
    trace's nanoseconds."""
    from repro.obs.trace import PREFIX, Span

    roots, stack = [], []
    for name, s, e, stats in events:
        if not name.startswith(PREFIX):
            continue
        sp = Span(name[len(PREFIX):], **stats)
        sp.t0, sp.wall_ms = s, (e - s) / 1e6
        while stack and stack[-1][1] < e:
            stack.pop()
        (stack[-1][0].children if stack else roots).append(sp)
        stack.append((sp, e))
    return roots


@pytest.mark.parametrize("base", ["dispatch_ms", "fetch_ms", "hop_rows",
                                  "hop_gathers_per_row"])
def test_program_readers_give_finite_values_on_the_chip_spans(pt, base, monkeypatch):
    import importlib

    from repro.obs import trace as program

    sink = program.Tracer()
    sink.roots = _spans(pt.events)
    monkeypatch.setattr(program, "PROFILED", sink)
    lo, hi = pt.window()
    answered = 8 * len([r for r in sink.roots if r.name == "batch"])
    run = SimpleNamespace(trace=object(), traced_requests=lambda: answered,
                          record=SimpleNamespace(t0=lo, t_close=hi))
    v = importlib.import_module(f"bench.metrics.{base}").read(run)
    assert v is not None and math.isfinite(v) and v > 0


def test_accepted_readers_read_as_before_on_the_old_fixture():
    """The values these readers gave on the old fixture when it was
    recorded (``hop_kernel_ms`` per 40 requests, ``hop_roofline`` for
    1e9 useful bytes)."""
    from bench.metrics import device_idle_pct, hop_kernel_ms, hop_roofline, host_ms

    tr = T.load(str(OLD))
    run = SimpleNamespace(trace=tr, trace_window=tr.window(),
                          traced_requests=lambda: 40, useful_bytes=lambda: 1e9,
                          peaks={"hbm_bytes_per_s": 819e9})
    assert host_ms.read(run) == pytest.approx(2.4302716, rel=1e-9)
    assert hop_kernel_ms.read(run) == pytest.approx(7.9175835, rel=1e-9)
    assert hop_roofline.read(run) == pytest.approx(0.38535285701733907, rel=1e-9)
    assert device_idle_pct.read(run) == pytest.approx(3.7006812646807496, rel=1e-9)
    assert P.load(str(OLD)).kernels  # no labels there: read, not named
    assert all(label == "" for _, label, *_ in P.load(str(OLD)).kernels)


def test_host_path_split_per_batch(pt):
    lo, hi = pt.window()
    split = P.host_ms_per_batch(pt, lo, hi)
    assert set(split) >= {"batch", "args", "attempt", "dispatch", "device_wait",
                          "fetch", "hops"}
    assert "compile" not in split and "admit" not in split
    inner = split["dispatch"] + split["device_wait"] + split["fetch"] + split["hops"]
    assert split["args"] + inner <= split["attempt"] + split["args"] <= split["batch"]


def test_linking_survives_a_device_clock_behind_the_host():
    """A module run whose device start reads before its dispatch's host
    start (seen up to 1.2 ms on a v5e) links to that dispatch, not the one
    before it, and the offset it implies is reported."""
    ev = lambda n, s, e: (n, s, e, {})  # noqa: E731
    pt = P.ProgramTrace(
        events=[ev("bench.window", 0, 100e6),
                ev("repro.dispatch", 10e6, 11e6), ev("repro.device_wait", 11e6, 40e6),
                ev("repro.dispatch", 50e6, 51e6), ev("repro.device_wait", 51e6, 90e6)],
        # the host's (10.5, 39.5) and (50.2, 89) read 2.5 ms early
        modules=[("m", 8.0e6, 37.0e6), ("m", 47.7e6, 86.5e6)])
    links = P.link_modules(pt)
    assert [d[1] for _, d, _ in links] == [10e6, 50e6]
    clk = P.clock(pt, 0, 100e6)
    assert clk["linked"] == 2 and clk["consistent"]
    assert clk["worst_ms"] == pytest.approx(2.3) and clk["offset_ms"] == pytest.approx(-2.3)
