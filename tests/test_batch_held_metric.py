"""The benchmark's ``live.batch_held_share.decision`` metric
(``bench/metrics/live.batch_held_share.decision.py``) on hand-made span
records: the share of a paper model's ``live.serve`` spans whose ``drawn``
arg reads 0, and nothing where there is nothing to read."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.workloads import load_module  # noqa: E402
from repro import tracing  # noqa: E402

NAME = "live.batch_held_share.decision"
MS = 1_000_000


def _records(drawn=(1, 0, 0, 0), llm=2):
    """Paper-model queries with the given ``drawn`` args, each with its
    ``live.execute``, then ``llm`` LLM queries (no ``drawn``)."""
    rows = []
    for d in drawn:
        rows += [("live.execute", {"batch": 4}),
                 ("live.serve", {"batch": 3, "drawn": d})]
    for _ in range(llm):
        rows += [("live.prefill", {"batch": 8}), ("live.decode", {"steps": 4}),
                 ("live.serve", {"batch": 5})]
    return [tracing.Record(name, i * MS, (i + 1) * MS, i + 1, None, None,
                           args)
            for i, (name, args) in enumerate(rows)]


def _read():
    return load_module(ROOT / "bench" / "metrics" / f"{NAME}.py",
                       "bench_metric_").read(None)


def _collect(monkeypatch, recs, dropped=0):
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    monkeypatch.setattr(tracing, "dropped", lambda: dropped)


@pytest.mark.parametrize("drawn,share", [((1, 0, 0, 0), 0.75),
                                         ((0,) * 9, 1.0), ((1, 1), 0.0)])
@pytest.mark.parametrize("llm", [0, 3])
def test_share_of_held_batches(monkeypatch, drawn, share, llm):
    """LLM ``live.serve`` spans, which carry no ``drawn``, do not count."""
    _collect(monkeypatch, _records(drawn, llm))
    assert _read() == pytest.approx(share)


@pytest.mark.parametrize("recs", [
    _records(drawn=(), llm=3),
    [r._replace(args={k: v for k, v in r.args.items() if k != "drawn"})
     for r in _records()],
    [],
], ids=["llm-only", "no-drawn-arg", "nothing"])
def test_spans_without_the_arg_read_nothing(monkeypatch, recs):
    """A program whose spans carry no ``drawn`` (one that draws every
    query's batch anew) reads as nothing, and raises nothing."""
    _collect(monkeypatch, recs)
    assert _read() is None


def test_a_ring_that_dropped_reads_nothing(monkeypatch):
    _collect(monkeypatch, _records(), dropped=1)
    assert _read() is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read() is None
