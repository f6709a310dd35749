"""The benchmark's comparison catches faults on a pipelined sweep.

A capacity sweep issues every grid dispatch before it fetches the first,
so its rates come out of ``PoolSimulator._qos_grid_fetch``, not out of
``PoolSimulator.qos``.  Faults planted where a sweep's rates are made
must still make the ``candle-sweep`` cell's run report ``correct`` false,
and the same run with nothing planted must report ``correct`` true:

* ``half_mean``: half of the lanes of a grid dispatch stand in for the
  other half;
* ``answer_altered``: each QoS rate is lowered by 0.01 where it is made.

The run is the benchmark's own (set-up, one unit, the comparison with the
reference), driven on the CPU at the cell's size.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from repro.serving.simulator import PoolSimulator  # noqa: E402


def _plant(monkeypatch, fault: str) -> None:
    fetch = PoolSimulator._qos_grid_fetch

    if fault == "half_mean":
        def broken(self, pending, queued=0):
            rates = fetch(self, pending, queued)
            n = rates.shape[-1]
            return rates[..., np.arange(n) % max(n // 2, 1)]
    elif fault == "answer_altered":
        def broken(self, pending, queued=0):
            return np.maximum(fetch(self, pending, queued) - 0.01, 0.0)
    else:
        raise ValueError(fault)
    monkeypatch.setattr(PoolSimulator, "_qos_grid_fetch", broken)


@pytest.mark.parametrize("fault", [None, "half_mean", "answer_altered"])
def test_sweep_fault_is_not_correct(monkeypatch, fault):
    if fault is not None:
        _plant(monkeypatch, fault)
    cell = run.Cell.load(run.ROOT, "candle-sweep")
    result = run.run_cell(cell, 2**31 + 20260917, 0.0, False,
                          require_chip=False)
    assert result["correct"] is (fault is None), result["check"]
