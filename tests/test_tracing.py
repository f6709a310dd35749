"""Program spans (``repro.tracing``).

Contracts under test:

* with no JAX profiler session recording, the program's calls leave no
  record, and an off span is one shared object;
* under a session, a grid sweep, a short search and a two-chunk stream
  record every span of the catalog, with the parent and the request id
  each should have, and the spans reach the profiler's host plane by name;
* collecting changes no answer: QoS rates, search picks and stream counts
  are bit-identical with and without a session;
* a garbage collection inside a session is a ``host.gc`` span;
* the ring keeps at most its capacity and counts what it pushed out;
* the scenario engine's search clocks are ``scenario.search`` spans whose
  wall durations feed its ``TraceRecorder``;
* no program span takes a name the benchmark's own spans use;
* a sweep of several grid dispatches stages them all before its first
  wait, and each wait counts the dispatches still queued behind it.
"""

import dataclasses
import gc
import glob
import re
from collections import deque
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import RibbonOptimizer
from repro.core.search_space import SearchSpace
from repro.scenario import (PhaseSpec, ScenarioEngine, ScenarioSpec,
                            SimulatorPlane, TraceRecorder)
from repro.serving import StreamingSimulator, make_paper_setup
from repro.serving.instance import InstanceType, ModelProfile
from repro.serving.pool import PoolEvaluator, paper_spec
from repro.serving.workload import generate_workload

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

# The spans the search, the evaluator and the simulator lanes record.
CALL_SPANS = {"ribbon.ask", "ribbon.select", "ribbon.tell", "pool.eval",
              "pool.memo", "sim.stage", "sim.wait", "sim.stream_draw"}
# The benchmark's own spans around its calls into the program
# (bench/workloads.py, bench/kinds/, bench/run.py): its reduction keeps
# host events by name, so a program span of one of these names would move
# its attribution of idle gaps.
RESERVED = {"decision", "stream.realize", "ask", "oracle", "tell", "sweep",
            "sweep.dispatch", "stream", "stream.chunk", "trace.window",
            "verify"}


def _calls():
    """A grid sweep, a search of 6 samples (single and batched oracle
    calls) and a two-chunk stream; their answers and the objects whose
    request ids the spans carry."""
    sweep_ev, space, profile = make_paper_setup("mtwnd", n_queries=200)
    grid = sweep_ev.grid(space.enumerate()[:12], (1.0, 1.25))
    ev, _, _ = make_paper_setup("mtwnd", n_queries=200)
    opt = RibbonOptimizer(space, start=(5, 0, 0))
    told = []
    for q in (1, 1, 2, 2):
        configs = opt.ask_batch(q)
        rates = ev.batch(configs) if len(configs) > 1 else [ev(configs[0])]
        for config, rate in zip(configs, rates):
            opt.tell(config, float(rate))
            told.append((config, float(rate)))
    spec = dataclasses.replace(paper_spec("mtwnd", seed=3), chunk=256)
    stream_sim = StreamingSimulator(profile, ev.types, spec)
    stream = stream_sim.qos((5, 1, 1), 512)
    answers = {"grid": grid, "told": told, "stream": stream}
    owners = {"sweep": sweep_ev, "search": opt, "oracle": ev,
              "stream": stream_sim}
    return answers, owners


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The calls once with no session and once under one."""
    tracing.clear()
    plain, _ = _calls()
    plain_records = tracing.records()
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        answers, owners = _calls()
    recs = tracing.records()
    tracing.clear()
    return {"plain": plain, "plain_records": plain_records,
            "answers": answers, "owners": owners, "records": recs,
            "dir": out}


def _by_name(recs, name, **args):
    return [r for r in recs if r.name == name
            and all(r.args.get(k) == v for k, v in args.items())]


def test_no_session_leaves_no_record(traced):
    assert traced["plain_records"] == []
    assert tracing.span("sim.stage") is tracing.span("pool.memo", lane="x")


def test_session_records_every_span(traced):
    names = {r.name for r in traced["records"]}
    assert CALL_SPANS <= names
    assert tracing.dropped() == 0


def test_search_spans_parent_and_request(traced):
    recs, opt = traced["records"], traced["owners"]["search"]
    sid = {r.sid: r for r in recs}
    asks, tells = _by_name(recs, "ribbon.ask"), _by_name(recs, "ribbon.tell")
    assert len(asks) == 4 and len(tells) == 6
    for r in asks + tells:
        assert r.parent is None and r.request == opt.request
    selects = _by_name(recs, "ribbon.select")
    # The first ask is the start pool, from the queue: no acquisition.
    assert len(selects) == 3
    for r in selects:
        assert sid[r.parent].name == "ribbon.ask"
        assert r.request == opt.request


def test_oracle_spans_parent_and_request(traced):
    recs, ev = traced["records"], traced["owners"]["oracle"]
    sid = {r.sid: r for r in recs}
    evals = _by_name(recs, "pool.eval")
    assert evals and all(r.request == ev.request for r in evals)
    eval_ids = {r.sid for r in evals}
    for lane in ("single", "batch"):
        for name in ("sim.stage", "sim.wait"):
            spans = _by_name(recs, name, lane=lane)
            assert spans, (name, lane)
            for r in spans:
                assert r.parent in eval_ids
                assert r.request == ev.request
    # Stage ends before its wait starts: the two do not overlap.
    for wait in _by_name(recs, "sim.wait", lane="single"):
        stage = [r for r in recs if r.parent == wait.parent
                 and r.name == "sim.stage"][0]
        assert stage.end_ns <= wait.start_ns
        assert sid[wait.parent].end_ns >= wait.end_ns


def test_sweep_and_stream_spans(traced):
    recs, owners = traced["records"], traced["owners"]
    sweep = owners["sweep"]
    # One dispatch of 12 pools x 2 loads: staging in the lane's entry and
    # in the count sweep, one wait, and the memo before and after.
    waits = _by_name(recs, "sim.wait", lane="grid")
    assert len(waits) == 1
    assert len(_by_name(recs, "sim.stage", lane="grid")) == 2
    assert len(_by_name(recs, "pool.memo")) == 3
    for name in ("pool.memo", "sim.stage", "sim.wait"):
        for r in _by_name(recs, name, **({} if name == "pool.memo"
                                         else {"lane": "grid"})):
            assert r.parent is None and r.request == sweep.request
    stream = owners["stream"]
    draws = _by_name(recs, "sim.stream_draw")
    assert len(draws) == 2
    (wait,) = _by_name(recs, "sim.wait", lane="stream")
    for r in draws + [wait]:
        assert r.parent is None and r.request == stream.request
    assert draws[-1].end_ns <= wait.start_ns


def test_spans_reach_the_profilers_host_plane(traced):
    (path,) = glob.glob(str(traced["dir"] / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes if plane.name.startswith(
        "/host") for line in plane.lines for ev in line.events}
    assert CALL_SPANS <= names


def test_collecting_changes_no_answer(traced):
    a, b = traced["plain"], traced["answers"]
    np.testing.assert_array_equal(a["grid"], b["grid"])
    assert a["told"] == b["told"]
    assert a["stream"] == b["stream"]


def test_gc_inside_a_session_is_a_span(tmp_path):
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("pool.memo", 41):
            gc.collect()
    recs = tracing.records()
    tracing.clear()
    (memo,) = _by_name(recs, "pool.memo")
    full = _by_name(recs, "host.gc", generation=2)
    assert full
    assert any(r.parent == memo.sid and r.request == 41 for r in full)
    assert all(r.end_ns >= r.start_ns for r in full)


def test_ring_keeps_its_capacity_and_counts_drops(monkeypatch, tmp_path):
    monkeypatch.setattr(tracing, "CAPACITY", 4)
    monkeypatch.setattr(tracing, "_ring", deque(maxlen=4))
    tracing.clear()
    gc.disable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for i in range(6):
                with tracing.span("pool.memo", i=i):
                    pass
    finally:
        gc.enable()
    assert [r.args["i"] for r in tracing.records()] == [2, 3, 4, 5]
    assert tracing.dropped() == 2
    tracing.clear()
    assert tracing.records() == [] and tracing.dropped() == 0


def test_timed_span_measures_with_no_session():
    tracing.clear()
    with tracing.timed("scenario.search", kind="initial") as span:
        sum(range(10000))
    assert span.seconds > 0
    assert tracing.records() == []


FAST = InstanceType("fast", price=1.0, flops=1e9, mem_bw=1e9, overhead=1e-3)
SLOW = InstanceType("slow", price=0.3, flops=2e8, mem_bw=5e8, overhead=2e-3)
PROF = ModelProfile("toy", flops_per_sample=1e6, act_bytes_per_sample=1e4,
                    weight_bytes=1e5, qos_latency=0.05)


def test_episode_searches_are_spans_feeding_the_recorder(tmp_path):
    wls = {"lognormal": generate_workload(0, 400, 120.0, median_batch=8.0,
                                          max_batch=32)}
    spec = ScenarioSpec(name="traced", phases=(PhaseSpec("steady", 400),),
                        window=100, seed=0).validate()
    recorder = TraceRecorder()
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        ScenarioEngine(spec, SimulatorPlane(PROF, [FAST, SLOW], wls,
                                            max_instances=8),
                       SearchSpace(bounds=(4, 4), prices=(1.0, 0.3)),
                       trace=recorder).run()
    recs = tracing.records()
    tracing.clear()
    (initial,) = _by_name(recs, "scenario.search", kind="initial")
    (event,) = [e for e in recorder.events
                if e.get("name") == "search:initial"]
    wall_us = (initial.end_ns - initial.start_ns) / 1e3
    assert abs(event["dur"] - wall_us) <= 1
    assert event["args"]["wall_ms"] == pytest.approx(wall_us / 1e3)
    # The search's own spans run inside the episode's.
    inside = [r for r in recs if r.parent == initial.sid]
    assert {"ribbon.ask", "ribbon.tell"} <= {r.name for r in inside}


def test_no_program_span_takes_a_benchmark_name():
    called = re.compile(r'tracing\.(?:span|timed)\(\s*"([^"]+)"')
    names = {m for path in SRC.rglob("*.py")
             for m in called.findall(path.read_text())}
    names |= {"host.gc"}
    assert CALL_SPANS | {"scenario.search"} <= names
    assert not names & RESERVED


def test_sharded_grid_stages_before_its_one_wait(monkeypatch, tmp_path):
    """On several devices a grid dispatch stages in three places (the
    lane's entry, the count sweep, the lane split) and waits once; its
    counts stay those of one device."""
    ev, space, _ = make_paper_setup("candle", n_queries=150)
    configs, factors = space.enumerate()[:5], (1.0, 1.25, 1.5)
    base = ev.sim.qos(configs, workloads=factors).rates
    monkeypatch.setattr(jax, "local_device_count", lambda: 2)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        sharded = ev.sim.qos(configs, workloads=factors).rates
    recs = tracing.records()
    tracing.clear()
    np.testing.assert_array_equal(sharded, base)
    stages = _by_name(recs, "sim.stage", lane="grid")
    (wait,) = _by_name(recs, "sim.wait", lane="grid")
    assert len(stages) == 3
    assert all(r.end_ns <= wait.start_ns for r in stages)


def test_sweep_issues_every_dispatch_before_its_first_wait(monkeypatch,
                                                           tmp_path):
    """A sweep of four grid dispatches: every grid ``sim.stage`` ends
    before the first grid ``sim.wait`` starts, and the waits' ``queued``
    args read 3, 2, 1, 0."""
    ev, space, _ = make_paper_setup("candle", n_queries=150)
    monkeypatch.setattr(PoolEvaluator, "_chunk", 4)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        ev.grid(space.enumerate()[::97][:15], (1.0, 1.25))
    recs = tracing.records()
    tracing.clear()
    stages = _by_name(recs, "sim.stage", lane="grid")
    waits = sorted(_by_name(recs, "sim.wait", lane="grid"),
                   key=lambda r: r.start_ns)
    assert len(waits) == 4 and len(stages) == 2 * len(waits)
    assert max(r.end_ns for r in stages) <= waits[0].start_ns
    assert [r.args["queued"] for r in waits] == [3, 2, 1, 0]
    for r in stages + waits:
        assert r.parent is None and r.request == ev.request
    # Each wait is followed by its memo writes, before the next wait.
    memos = _by_name(recs, "pool.memo")
    for a, b in zip(waits, waits[1:]):
        assert any(a.end_ns <= m.start_ns and m.end_ns <= b.start_ns
                   for m in memos)
