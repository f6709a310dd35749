"""The readers of the program's own spans (``bench/program_spans.py``) and
the metrics that use them, on a hand-made record list."""

import json
import sys

import pytest

from bench import program_spans as ps
from bench.run import ROOT
from bench.workloads import load_module
from repro import tracing

MS = 1_000_000


def _records():
    rows = [
        # sid, parent, name, start ms, end ms, args
        (1, None, "ribbon.ask", 0, 10, {}),
        (2, 1, "ribbon.select", 2, 6, {}),
        (3, None, "ribbon.tell", 10, 12, {}),
        (4, None, "pool.eval", 12, 20, {}),
        (5, 4, "sim.stage", 12, 13, {"lane": "single"}),
        (6, 4, "sim.wait", 13, 19, {"lane": "single"}),
        (7, None, "ribbon.ask", 20, 23, {}),
        (8, None, "ribbon.tell", 23, 24, {}),
        (9, None, "pool.memo", 30, 31, {}),
        (10, None, "sim.stage", 31, 33, {"lane": "grid"}),
        (11, None, "sim.stage", 33, 33.5, {"lane": "grid"}),
        (12, None, "sim.wait", 33.5, 40, {"lane": "grid"}),
        (13, None, "pool.memo", 40, 42, {}),
        (14, None, "sim.stage", 42, 43, {"lane": "grid"}),
        (15, None, "sim.wait", 43, 50, {"lane": "grid"}),
        (16, None, "pool.memo", 50, 51, {}),
        (17, None, "sim.stream_draw", 60, 61.5, {}),
        (18, None, "sim.stream_draw", 62, 63.5, {}),
        (19, None, "host.gc", 70, 75, {"generation": 2}),
    ]
    return [tracing.Record(name, int(t0 * MS), int(t1 * MS), sid, parent,
                           None, args)
            for sid, parent, name, t0, t1, args in rows]


# Hand-computed from the list above.
EXPECTED = {
    # 4 ms of select over 2 tells.
    "search.select_ms": 2.0,
    # (10 + 3 ms of ask, 2 + 1 of tell, less 4 of select) over 2 tells.
    "search.host_ms": 6.0,
    # 8 ms of eval less its 6-ms wait, one eval.
    "pool.oracle_host_ms": 2.0,
    # 4 ms of memo, 3.5 ms of grid staging, 2 grid waits.
    "pool.memo_ms_per_dispatch": 2.0,
    "pool.memo_ms_per_dispatch.x4": 2.0,
    "sim.stage_ms_per_dispatch": 1.75,
    "sim.stage_ms_per_dispatch.x4": 1.75,
    # 3 ms over 2 chunks.
    "sim.stream_draw_ms_per_chunk": 1.5,
    # 5 ms of collection over the 75 ms from the first record to the last.
    "host.gc_ms_per_s.decision": 5 / 0.075,
    "host.gc_ms_per_s.scored": 5 / 0.075,
    "host.gc_ms_per_s.scored.x4": 5 / 0.075,
    "host.gc_ms_per_s.scored.stream": 5 / 0.075,
}


def _read(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_").read(None)


def _collect(monkeypatch, recs, dropped=0):
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    monkeypatch.setattr(tracing, "dropped", lambda: dropped)


def test_every_program_span_metric_is_declared():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"] for m in spec["per_layer"]
                if m["source"] == "program_span"}
    assert declared == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_records(monkeypatch, name):
    _collect(monkeypatch, _records())
    assert _read(name) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_records(monkeypatch, name):
    _collect(monkeypatch, [])
    assert _read(name) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_refuses_a_ring_that_dropped(monkeypatch, name):
    _collect(monkeypatch, _records(), dropped=1)
    assert _read(name) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_a_program_without_spans(monkeypatch, name):
    """A program from before the registry reads as nothing, and raises
    nothing."""
    import repro

    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name) is None


def test_ratio_without_its_denominator_is_nothing(monkeypatch):
    recs = [r for r in _records() if r.name != "ribbon.tell"]
    _collect(monkeypatch, recs)
    assert _read("search.select_ms") is None


def test_self_time_subtracts_only_the_named_children():
    recs = _records()
    assert ps.self_ms(recs, "pool.eval", ("sim.wait",)) == pytest.approx(2)
    assert ps.self_ms(recs, "pool.eval", ("sim.wait", "sim.stage")) == (
        pytest.approx(1))
