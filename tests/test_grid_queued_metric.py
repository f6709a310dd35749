"""The benchmark's ``sim.grid_queued_per_wait`` metrics
(``bench/metrics/sim.grid_queued_per_wait.py`` and its ``.x4`` twin) on
hand-made span records: the mean ``queued`` arg of the grid lane's
``sim.wait`` spans, and nothing where there is nothing to read."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.workloads import load_module  # noqa: E402
from repro import tracing  # noqa: E402

NAMES = ("sim.grid_queued_per_wait", "sim.grid_queued_per_wait.x4")
MS = 1_000_000


def _records(queued=(3, 2, 1, 0)):
    """A sweep of ``len(queued)`` grid dispatches, all staged before the
    first wait, beside a single-lane call and a memo span."""
    rows = [("sim.stage", {"lane": "grid"})] * len(queued)
    rows += [("sim.wait", {"lane": "grid", "queued": q}) for q in queued]
    rows += [("pool.memo", {}), ("sim.stage", {"lane": "single"}),
             ("sim.wait", {"lane": "single"})]
    return [tracing.Record(name, i * MS, (i + 1) * MS, i + 1, None, None,
                           args)
            for i, (name, args) in enumerate(rows)]


def _read(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_").read(None)


def _collect(monkeypatch, recs, dropped=0):
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    monkeypatch.setattr(tracing, "dropped", lambda: dropped)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("queued,mean", [((3, 2, 1, 0), 1.5), ((0,), 0.0),
                                         (tuple(range(33, -1, -1)), 16.5)])
def test_mean_queued_over_grid_waits(monkeypatch, name, queued, mean):
    _collect(monkeypatch, _records(queued))
    assert _read(name) == pytest.approx(mean)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_collected_reads_nothing(monkeypatch, name):
    _collect(monkeypatch, [])
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_ring_that_dropped_reads_nothing(monkeypatch, name):
    _collect(monkeypatch, _records(), dropped=1)
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_waits_without_the_arg_read_nothing(monkeypatch, name):
    """A program whose grid waits carry no ``queued`` arg (one from before
    the pipelined sweep) reads as nothing, and raises nothing."""
    recs = [r._replace(args={k: v for k, v in r.args.items()
                             if k != "queued"}) for r in _records()]
    _collect(monkeypatch, recs)
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name) is None
