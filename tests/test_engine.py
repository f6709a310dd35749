"""Live execution plane: ClusterEngine with real (smoke-scale) models."""

import jax
import numpy as np
import pytest

from repro import tracing
from repro.models.paper_models import make_random_batch
from repro.serving import engine as engine_mod
from repro.serving.autoscaler import LoadMonitor
from repro.serving.engine import CellType, ClusterEngine
from repro.serving.workload import Workload, generate_workload

CELLS = [CellType("cell1", price=1.2, chips=1, speed=1.0),
         CellType("cell4", price=4.8, chips=4, speed=3.0)]


@pytest.fixture(scope="module")
def engine():
    eng = ClusterEngine("mtwnd", CELLS, seed=0)
    return eng


def test_configure_and_price(engine):
    engine.configure((2, 1))
    assert len(engine.cells) == 3
    assert engine.pool_price() == pytest.approx(2 * 1.2 + 4.8)
    assert engine.pool_price((1, 2)) == pytest.approx(1.2 + 9.6)


def test_serve_real_queries(engine):
    engine.configure((2, 1))
    wl = generate_workload(0, 30, rate_qps=50.0, median_batch=4, max_batch=16)
    rate = engine.serve(wl, qos_latency=10.0, time_scale=1.0)
    assert 0.0 <= rate <= 1.0
    assert len(engine.records) == 30
    # every query actually executed on some cell
    assert sum(c.n_served for c in engine.cells) >= 30
    # with an absurdly generous target everything satisfies
    assert engine.serve(wl, qos_latency=1e6) == 1.0


def test_fail_cell_shrinks_pool(engine):
    engine.configure((2, 1))
    lost = engine.fail_cell(0)
    assert lost.name == "cell1"
    assert engine.active_config() == (1, 1)
    wl = generate_workload(1, 10, rate_qps=20.0, median_batch=4, max_batch=8)
    rate = engine.serve(wl, qos_latency=1e6)
    assert rate == 1.0   # surviving cells still serve everything


def test_empty_pool_serves_nothing(engine):
    engine.configure((0, 0))
    wl = generate_workload(2, 5, rate_qps=10.0, median_batch=4, max_batch=8)
    assert engine.serve(wl, qos_latency=1.0) == 0.0


def test_serve_records_waits_and_feeds_monitor(engine):
    """The measured plane exposes (latencies, waits) windows so the load
    monitor works on real records, not just the simulator."""
    engine.configure((2, 1))
    wl = generate_workload(4, 30, rate_qps=200.0, median_batch=4,
                           max_batch=16)
    engine.serve(wl, qos_latency=10.0)
    lat, waits = engine.served_arrays()
    assert lat.shape == waits.shape == (30,)
    assert (waits >= 0).all()
    assert (lat >= waits).all()           # wait is part of the latency
    assert all(r.wait >= 0 for r in engine.records)
    mon = LoadMonitor(qos_target=0.99)
    assert mon.observe(lat, waits, qos_latency=10.0) is False   # baseline
    assert isinstance(mon.observe(lat, waits, 10.0), bool)


def test_empty_pool_clears_stale_records(engine):
    engine.configure((2, 1))
    wl = generate_workload(5, 8, rate_qps=20.0, median_batch=4, max_batch=8)
    engine.serve(wl, qos_latency=1e6)
    engine.configure((0, 0))
    assert engine.serve(wl, qos_latency=1e6) == 0.0
    lat, waits = engine.served_arrays()
    assert lat.size == 0 and waits.size == 0


def test_preempt_hook(engine):
    engine.configure((2, 1))
    assert engine.preempt(0, 1) == 1
    assert engine.active_config() == (1, 1)
    assert engine.preempt(1, 5) == 1      # only one cell4 to reclaim
    assert engine.active_config() == (1, 0)
    assert engine.preempt(1, 1) == 0      # nothing left of that type
    # re-provisioning clears the preempted pool
    engine.configure((1, 1))
    assert engine.active_config() == (1, 1)


def test_type_order_priority_live(engine):
    """First idle cell in pool-type order takes the query (paper §5.1)."""
    engine.configure((1, 1))
    wl = generate_workload(3, 6, rate_qps=0.01, median_batch=2, max_batch=4)
    engine.serve(wl, qos_latency=1e6)
    # with fully spaced arrivals every query lands on the first type
    assert all(r.cell == "cell1" for r in engine.records)


def test_serve_warm_start_initial_busy(engine):
    """`initial_busy` warm-starts the virtual clock: a carried backlog
    delays every start, the per-query slot trace names the advanced cell,
    and a mismatched vector is rejected."""
    engine.configure((2, 1))
    wl = generate_workload(6, 15, rate_qps=100.0, median_batch=4,
                           max_batch=8)
    engine.serve(wl, qos_latency=1e6)
    assert all(0 <= r.slot < 3 for r in engine.records)
    # every cell starts 5 virtual seconds busy: all queries arrive earlier
    # (span ~0.15s at 100 qps) and must queue behind the carried work
    engine.serve(wl, qos_latency=1e6, initial_busy=[5.0, 5.0, 5.0])
    _, waits = engine.served_arrays()
    assert (waits >= 4.0).all()
    assert all(c.busy_until >= 5.0 for c in engine.cells)
    with pytest.raises(ValueError):
        engine.serve(wl, qos_latency=1e6, initial_busy=[5.0])


def _count_draws(monkeypatch) -> list:
    """Route the engine's ``make_random_batch`` through a recorder; returns
    the list its calls' buckets land in."""
    calls = []

    def counted(model, preset, batch, seed=0):
        calls.append(batch)
        return make_random_batch(model, preset, batch, seed)

    monkeypatch.setattr(engine_mod, "make_random_batch", counted)
    return calls


def _stream(batches, gap=0.01) -> Workload:
    n = len(batches)
    return Workload(arrivals=np.arange(n) * gap,
                    batches=np.asarray(batches), rate_qps=1.0 / gap)


def test_warm_engine_draws_no_batch(monkeypatch, tmp_path):
    """After ``warmup`` every query's batch is held; a bucket beyond the
    warm-up draws on its first query only, and each paper-model
    ``live.serve`` span says which."""
    eng = ClusterEngine("mtwnd", CELLS, seed=0)
    eng.warmup(max_batch=8)
    eng.configure((2, 1))
    calls = _count_draws(monkeypatch)
    wl = generate_workload(7, 20, rate_qps=100.0, median_batch=4,
                           max_batch=8)
    eng.serve(wl, qos_latency=1e6)
    assert calls == []
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(_stream([12, 16, 3]), qos_latency=1e6)
    recs = tracing.records()
    tracing.clear()
    assert calls == [16]
    assert [r.args["drawn"] for r in recs if r.name == "live.serve"] == [
        1, 0, 0]


def test_unwarmed_engine_draws_each_bucket_once(monkeypatch):
    eng = ClusterEngine("mtwnd", CELLS[:1], seed=0)
    eng.configure((1,))
    calls = _count_draws(monkeypatch)
    eng.serve(_stream([3, 4, 1, 2, 4, 1]), qos_latency=1e6)
    assert calls == [4, 1, 2]


def _assert_parent_bits(eng, keep):
    """Each kept (batch, output) pair is the draw of its bucket and the
    serving cell type's ``apply`` on it, array for array."""
    assert sorted(eng.kept) == sorted(keep)
    for i, (batch, out) in eng.kept.items():
        rec = eng.records[i]
        bucket = 1 << int(np.ceil(np.log2(rec.batch_size)))
        want = make_random_batch("mtwnd", "smoke", bucket)
        assert batch.keys() == want.keys()
        for k in want:
            assert batch[k].dtype == want[k].dtype
            np.testing.assert_array_equal(np.asarray(batch[k]),
                                          np.asarray(want[k]))
        ref = eng._apply[rec.cell](eng._params[rec.cell], want)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_held_batches_serve_the_parent_bits():
    """Two cell types of one preset share the held batches, and a new pool
    (``configure``) serves the same bits as the first."""
    eng = ClusterEngine("mtwnd", CELLS, seed=0)
    eng.warmup(max_batch=16)
    # arrivals a microsecond apart: queries queue, so both types serve
    wl = _stream(generate_workload(8, 24, rate_qps=100.0, median_batch=4,
                                   max_batch=16).batches, gap=1e-6)
    keep = range(wl.n_queries)
    for pool in ((1, 1), (2, 1)):
        eng.configure(pool)
        eng.serve(wl, qos_latency=1e6, keep=keep)
        assert {r.cell for r in eng.records} == {"cell1", "cell4"}
        _assert_parent_bits(eng, keep)
