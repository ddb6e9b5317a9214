"""A run needs a TPU of a kind ``bench/peaks.json`` knows; otherwise it
exits non-zero and prints no result line."""
from types import SimpleNamespace

import pytest

from bench import run as R


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert R.main(["--workload", "pubmed-m-1m.term-pairs", "--seed", "1",
                   "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(R.NoDevice, match="not in peaks.json"):
        R.find_devices(1)


def test_too_few_chips_are_refused(monkeypatch):
    import jax

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(R.NoDevice, match="needs 4 chips"):
        R.find_devices(4)
    devs, peaks = R.find_devices(1)
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no program
    to run."""
    import shutil
    import subprocess
    import sys

    from bench.tests.common import ROOT

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pubmed-m-1m.term-pairs",
         "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "program" in out.stderr or "no TPU" in out.stderr, out.stderr
