"""The harness refuses to measure without the chips a cell asks for, and
without the program beside it."""

import json
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.run import ROOT


class _Dev:
    def __init__(self, platform):
        self.platform = platform
        self.device_kind = "TPU v5 lite" if platform == "tpu" else "cpu"


@pytest.mark.parametrize("platform,count,chips", [
    ("cpu", 1, 1), ("tpu", 4, 1), ("tpu", 1, 4), ("cpu", 4, 4)])
def test_main_exits_nonzero_without_the_cells_chips(monkeypatch, capsys,
                                                    platform, count, chips):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev(platform)] * count)
    cell = "candle-sweep" if chips == 1 else "candle-sweep-x4"
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_cli_exits_nonzero_on_cpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mtwnd-stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(ROOT)})
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_exits_nonzero_beside_nothing_but_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "candle-sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
