"""Joint (workload × config) grid engine + device-side pruning + rescale.

Three equivalence contracts of the PR:

* grid bit-identity — every cell of the ``simulate``/``qos`` grid lanes
  equals the single-config path bound to the scaled workload, bit for bit;
* device-side prune masks — the fused on-device tell update
  (``pruning.apply_prune_rules``) stays bit-identical to the host-side
  ``PruneSet`` + sampled mirrors over whole recorded BO runs;
* grid-driven ``rescale`` — the autoscaler-in-the-loop search lands on a
  configuration that is genuinely feasible under the scaled load;
* pipelined sweeps — an evaluator sweep of several dispatches, all issued
  before the first is fetched, returns and memoizes exactly what scoring
  each chunk through the blocking ``qos`` lane gives, on one device and
  on four.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RibbonOptimizer, select_batch
from repro.core.search_space import SearchSpace
from repro.serving.autoscaler import rescale
from repro.serving.instance import (InstanceType, ModelProfile,
                                    service_time_table)
from repro.serving.pool import PoolEvaluator, make_paper_setup
from repro.serving.routing import RoutingPolicy
from repro.serving.simulator import PoolSimulator, _qos_threshold_f32
from repro.serving.workload import generate_workload

FAST = InstanceType("fast", price=1.0, flops=1e9, mem_bw=1e9, overhead=1e-3)
SLOW = InstanceType("slow", price=0.3, flops=2e8, mem_bw=5e8, overhead=2e-3)
PROF = ModelProfile("toy", flops_per_sample=1e6, act_bytes_per_sample=1e4,
                    weight_bytes=1e5, qos_latency=0.05)

MAX_INST = 8
FACTORS = (1.0, 1.2, 1.5, 2.0)


def _workload(seed=0, n=200, rate=120.0):
    return generate_workload(seed, n, rate, median_batch=8.0, max_batch=32)


def _sim(wl=None):
    wl = wl or _workload()
    return PoolSimulator(PROF, [FAST, SLOW], wl, max_instances=MAX_INST)


def _scaled_sim(wl, factor):
    return PoolSimulator(PROF, [FAST, SLOW], wl.scaled(factor),
                         max_instances=MAX_INST)


def _configs(n=8, seed=0):
    rng = np.random.default_rng(seed)
    cfgs = rng.integers(0, 5, size=(n, 2))
    cfgs[0] = (0, 0)                              # empty pool
    cfgs[1] = (MAX_INST // 2, MAX_INST // 2)      # max-capacity padding
    return cfgs


# ----------------------------------------------------------- grid bit-identity
def test_latencies_grid_matches_scaled_single_exactly():
    """simulate(..., workloads=)[w, b] == the single lane of a simulator
    bound to workload.scaled(factor_w), bit for bit (4 x 8 grid)."""
    wl = _workload()
    sim = _sim(wl)
    cfgs = _configs()
    grid = sim.simulate(cfgs, workloads=FACTORS).lat
    assert grid.shape == (len(FACTORS), len(cfgs), wl.n_queries)
    for w, f in enumerate(FACTORS):
        scaled = _scaled_sim(wl, f)
        for b, cfg in enumerate(cfgs):
            single = scaled.simulate(tuple(int(c) for c in cfg)).lat
            np.testing.assert_array_equal(grid[w, b], single)


def test_qos_rate_grid_matches_scaled_single_exactly():
    """The acceptance grid: qos(...).rates[w, b] == the single rate of
    (workload_w, config_b) elementwise over a 4 x 8 grid."""
    wl = _workload(seed=3, n=150, rate=200.0)
    sim = _sim(wl)
    cfgs = _configs(seed=1)
    rates = sim.qos(cfgs, workloads=FACTORS).rates
    assert rates.shape == (len(FACTORS), len(cfgs))
    for w, f in enumerate(FACTORS):
        scaled = _scaled_sim(wl, f)
        for b, cfg in enumerate(cfgs):
            assert rates[w, b] == float(
                scaled.qos(tuple(int(c) for c in cfg)).rates)


def test_qos_rate_grid_matches_batch_rows():
    """Row w of the grid == the batch lane on the scaled simulator."""
    wl = _workload(seed=5)
    sim = _sim(wl)
    cfgs = _configs(seed=2)
    rates = sim.qos(cfgs, workloads=FACTORS).rates
    for w, f in enumerate(FACTORS):
        np.testing.assert_array_equal(
            rates[w], _scaled_sim(wl, f).qos(cfgs).rates)


def test_grid_unit_factor_row_matches_unscaled_paths():
    sim = _sim()
    cfgs = _configs(seed=4)
    rates = sim.qos(cfgs, workloads=(1.0,)).rates
    np.testing.assert_array_equal(rates[0], sim.qos(cfgs).rates)
    lat = sim.simulate(cfgs, workloads=(1.0,)).lat
    np.testing.assert_array_equal(lat[0], sim.simulate(cfgs).lat)


def test_grid_empty_and_zero_configs():
    sim = _sim()
    empty = sim.simulate(np.zeros((0, 2), dtype=np.int64),
                         workloads=FACTORS).lat
    assert empty.shape == (len(FACTORS), 0, sim.workload.n_queries)
    assert sim.qos(np.zeros((0, 2), dtype=np.int64),
                   workloads=FACTORS).rates.shape == (len(FACTORS), 0)
    # the all-zero config row: +inf latencies, zero satisfaction
    grid = sim.simulate([(0, 0)], workloads=FACTORS).lat
    assert np.isinf(grid).all()
    assert (sim.qos([(0, 0)], workloads=FACTORS).rates == 0.0).all()


def test_grid_rejects_bad_load_factors():
    sim = _sim()
    with pytest.raises(ValueError):
        sim.qos([(1, 1)], workloads=[])
    with pytest.raises(ValueError):
        sim.qos([(1, 1)], workloads=[0.0])
    with pytest.raises(ValueError):
        sim.qos([(1, 1)], workloads=[-1.5])
    with pytest.raises(ValueError):
        sim.simulate([(1, 1)], workloads=[np.inf])


def test_grid_arr_shards_pads_cyclically_beyond_workload_count():
    """The workload-axis pad may exceed W (one load level on an 8-device
    host): rows must wrap cyclically instead of silently under-filling the
    device multiple.  shard_map takes global operands, so the cached array
    keeps its 2-D shape — padded to a device multiple and laid out over the
    lane mesh."""
    sim = _sim()
    for n_w, n_dev in [(1, 4), (2, 8), (3, 4), (5, 8), (4, 4)]:
        factors = tuple(1.0 + 0.1 * i for i in range(n_w))
        arr = np.asarray(sim._stacked_arrivals(factors), np.float32)
        out = np.asarray(sim._grid_arr_shards(arr, "w", n_dev, factors))
        pad_w = (-n_w) % n_dev
        assert out.shape == (n_w + pad_w, sim.workload.n_queries)
        for i in range(n_w + pad_w):
            np.testing.assert_array_equal(out[i], arr[i % n_w])


@pytest.mark.slow
def test_grid_bit_identity_under_forced_multi_device(tmp_path):
    """the grid qos lane must survive (and stay exact on) hosts where
    benchmarks/__init__.py forces many XLA host devices — including the
    W=1, odd-B case whose workload-axis pad exceeds W."""
    import os
    import subprocess
    import sys
    script = tmp_path / "grid_multidev.py"
    script.write_text(
        "import numpy as np\n"
        "from repro.serving.simulator import PoolSimulator\n"
        "from repro.serving.instance import InstanceType, ModelProfile\n"
        "from repro.serving.workload import generate_workload\n"
        "import jax\n"
        "assert jax.local_device_count() == 4\n"
        "fast = InstanceType('fast', price=1.0, flops=1e9, mem_bw=1e9,\n"
        "                    overhead=1e-3)\n"
        "slow = InstanceType('slow', price=0.3, flops=2e8, mem_bw=5e8,\n"
        "                    overhead=2e-3)\n"
        "prof = ModelProfile('toy', flops_per_sample=1e6,\n"
        "                    act_bytes_per_sample=1e4, weight_bytes=1e5,\n"
        "                    qos_latency=0.05)\n"
        "wl = generate_workload(0, 100, 120.0, median_batch=8.0,\n"
        "                       max_batch=32)\n"
        "sim = PoolSimulator(prof, [fast, slow], wl, max_instances=8)\n"
        "cfgs = np.array([[1, 0], [2, 1], [0, 3]])  # odd B\n"
        "for factors in [(1.5,), (1.0, 1.2), (1.0, 1.2, 1.5)]:\n"
        "    got = sim.qos(cfgs, workloads=factors).rates\n"
        "    for w, f in enumerate(factors):\n"
        "        ref = PoolSimulator(prof, [fast, slow], wl.scaled(f),\n"
        "                            max_instances=8).qos(cfgs).rates\n"
        "        np.testing.assert_array_equal(got[w], ref)\n"
        "print('MULTIDEV-OK')\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(__import__("pathlib").Path(
                              __file__).resolve().parent.parent))
    assert proc.returncode == 0, proc.stderr
    assert "MULTIDEV-OK" in proc.stdout


def test_grid_stacked_service_tables_match_per_dist_sims():
    """The per-workload service-table axis: row w of the grid with stacked
    tables equals a simulator bound to that row's batch stream (same
    arrivals, different batches), bit for bit, on both grid paths."""
    wl_ln = _workload(seed=2, n=150, rate=150.0)
    wl_ga = generate_workload(2, 150, 150.0, batch_dist="gaussian",
                              mean_batch=10.0, std_batch=4.0, max_batch=32)
    np.testing.assert_array_equal(wl_ln.arrivals, wl_ga.arrivals)
    sim = _sim(wl_ln)
    cfgs = _configs(seed=6)
    tables = np.stack([
        service_time_table(PROF, [FAST, SLOW], wl_ln.batches),
        service_time_table(PROF, [FAST, SLOW], wl_ga.batches)])
    factors = (1.0, 1.5)
    rates = sim.qos(cfgs, workloads=factors, service_tables=tables).rates
    lat = sim.simulate(cfgs, workloads=factors,
                       service_tables=tables).lat
    for w, (f, wl) in enumerate(zip(factors, (wl_ln, wl_ga))):
        ref = PoolSimulator(PROF, [FAST, SLOW], wl.scaled(f),
                            max_instances=MAX_INST)
        np.testing.assert_array_equal(rates[w], ref.qos(cfgs).rates)
        np.testing.assert_array_equal(lat[w], ref.simulate(cfgs).lat)


def test_grid_stacked_service_tables_shape_validated():
    sim = _sim()
    nq = sim.workload.n_queries
    with pytest.raises(ValueError):        # W mismatch
        sim.qos([(1, 1)], workloads=(1.0, 1.5),
                service_tables=np.zeros((1, 2, nq)))
    with pytest.raises(ValueError):        # type-axis mismatch
        sim.simulate([(1, 1)], workloads=(1.0,),
                     service_tables=np.zeros((1, 3, nq)))
    with pytest.raises(ValueError):        # query-axis mismatch
        sim.qos([(1, 1)], workloads=(1.0,),
                service_tables=np.zeros((1, 2, nq - 1)))


def test_latencies_waits_consistent_with_latencies():
    sim = _sim()
    for cfg in [(2, 1), (1, 0)]:
        r = sim.simulate(cfg)
        lat, waits = r.lat, r.waits
        np.testing.assert_array_equal(lat, sim.simulate(cfg).lat)
        assert (waits >= 0).all()
        assert np.isfinite(waits).all()
        assert (waits <= lat).all()        # wait is part of the latency
    r0 = sim.simulate((0, 0))
    lat, waits = r0.lat, r0.waits
    assert np.isinf(lat).all() and np.isinf(waits).all()


def test_qos_threshold_f32_admits_same_latency_set():
    """The rounded-down float32 target classifies every float32 latency
    exactly as the float64 host comparison does."""
    for qos in (0.02, 0.03, 0.04, 0.4, 0.8, 0.05):
        t = _qos_threshold_f32(qos)
        probes = np.array([qos, t], dtype=np.float32)
        probes = np.concatenate([probes,
                                 np.nextafter(probes, np.float32(np.inf)),
                                 np.nextafter(probes, np.float32(-np.inf))])
        for x in probes:
            assert (float(x) <= qos) == (x <= np.float32(t))


# ------------------------------------------------------------- evaluator grid
def test_evaluator_grid_consistent_with_call_and_memoized():
    ev = PoolEvaluator(PROF, [FAST, SLOW], _workload(n=150, rate=150.0),
                       max_instances=MAX_INST)
    cfgs = [(1, 0), (2, 1), (0, 3), (1, 0)]       # includes a duplicate
    rates = ev.grid(cfgs, FACTORS)
    assert rates.shape == (len(FACTORS), len(cfgs))
    np.testing.assert_array_equal(rates[:, 0], rates[:, 3])
    n_after_grid = ev.n_evals
    assert n_after_grid == 3 * len(FACTORS)       # distinct cells only
    # unit-factor row shares the plain memo: no new evaluations
    for cfg, rate in zip(cfgs, rates[0]):
        assert rate == ev(cfg)
    assert ev.n_evals == n_after_grid
    # repeat grid: fully cached
    np.testing.assert_array_equal(ev.grid(cfgs, FACTORS), rates)
    assert ev.n_evals == n_after_grid
    # a subset at a subset of factors: still fully cached
    sub = ev.grid(cfgs[:2], FACTORS[1:3])
    np.testing.assert_array_equal(sub, rates[1:3, :2])
    assert ev.n_evals == n_after_grid


def test_evaluator_grid_matches_scaled_evaluator():
    wl = _workload(seed=7, n=150, rate=150.0)
    ev = PoolEvaluator(PROF, [FAST, SLOW], wl, max_instances=MAX_INST)
    hot = PoolEvaluator(PROF, [FAST, SLOW], wl.scaled(1.5),
                        max_instances=MAX_INST)
    cfgs = [(2, 0), (1, 2), (3, 3)]
    rates = ev.grid(cfgs, [1.5])[0]
    for cfg, rate in zip(cfgs, rates):
        assert rate == hot(cfg)


# -------------------------------------------------------- device-side pruning
SPACE = SearchSpace(bounds=(6, 8), prices=(1.0, 0.35))


def _oracle(config):
    cap = float(np.dot((10.0, 3.0), np.asarray(config, dtype=np.float64)))
    return min(1.0, cap / 33.0)


def _assert_masks_equal(opt):
    np.testing.assert_array_equal(np.asarray(opt._blocked_dev),
                                  opt.sampled | opt.prune.mask)


def test_device_mask_tracks_host_pruneset_over_bo_run():
    """Over a recorded BO run, the device-resident blocked mask stays
    bit-identical to the host PruneSet|sampled after every tell (both prune
    rules fire along the way: feasible incumbents and >θ violators)."""
    opt = RibbonOptimizer(SPACE, qos_target=0.99)
    fired = {"down": False, "cost": False}
    for _ in range(20):
        cfg = opt.ask()
        if cfg is None:
            break
        rate = _oracle(cfg)
        fired["cost" if rate >= 0.99 else "down"] = True
        opt.tell(cfg, rate)
        _assert_masks_equal(opt)
    assert fired["cost"] and fired["down"]


def test_device_mask_tracks_host_after_warm_restart():
    opt = RibbonOptimizer(SPACE, qos_target=0.99)
    for _ in range(8):
        cfg = opt.ask()
        opt.tell(cfg, _oracle(cfg))
    opt.warm_restart(new_qos_of_best=0.7)
    _assert_masks_equal(opt)
    for _ in range(5):
        cfg = opt.ask()
        if cfg is None:
            break
        opt.tell(cfg, 0.8 * _oracle(cfg))
        _assert_masks_equal(opt)


def test_device_mask_rebuilt_on_state_restore():
    opt = RibbonOptimizer(SPACE, qos_target=0.99)
    for _ in range(6):
        cfg = opt.ask()
        opt.tell(cfg, _oracle(cfg))
    state = opt.state_dict()
    fresh = RibbonOptimizer(SPACE, qos_target=0.99)
    fresh.load_state_dict(state)
    _assert_masks_equal(fresh)
    assert fresh.ask() == opt.ask()


def test_select_batch_returns_updated_mask():
    """select_batch takes the device mask and returns it with the q picks
    marked — a strict superset of the input mask."""
    opt = RibbonOptimizer(SPACE, qos_target=0.99)
    for _ in range(4):
        cfg = opt.ask()
        opt.tell(cfg, _oracle(cfg))
    x, y, mask = opt.gp.buffers()
    blocked_in = opt._blocked_dev
    picks, scores, blocked_out = select_batch(
        x, y, mask, opt._lattice_dev, opt.gp.denom,
        float(opt.best_objective_observed()), blocked_in,
        opt._weights_dev, 4)
    picks = np.asarray(picks)
    b_in, b_out = np.asarray(blocked_in), np.asarray(blocked_out)
    assert b_out[picks].all()
    assert (b_out | b_in).sum() == b_out.sum()     # superset
    assert b_out.sum() == b_in.sum() + len(set(picks.tolist()))
    # taking-and-returning leaves the optimizer's own mask untouched (ask
    # stays idempotent until the matching tells arrive)
    assert opt.ask_batch(3) == opt.ask_batch(3)
    _assert_masks_equal(opt)


# ------------------------------------------------------------ rescale on grid
def test_rescale_grid_integration():
    """rescale with load_factors drives the grid path end-to-end: the new
    optimum is feasible under the scaled load, and qos_by_load reports every
    monitored level from cache."""
    wl = _workload(seed=0, n=200, rate=120.0)
    ev = PoolEvaluator(PROF, [FAST, SLOW], wl, max_instances=MAX_INST)
    space = SearchSpace(bounds=(4, 4), prices=(1.0, 0.3))
    opt = RibbonOptimizer(space, qos_target=0.9)
    for _ in range(25):
        cfg = opt.ask()
        if cfg is None or opt.done:
            break
        opt.tell(cfg, ev(cfg))
    assert opt.best_config is not None

    n_before = ev.n_evals
    event = rescale(opt, ev, budget=25, load_factors=(1.0, 1.5))
    assert event.new_best is not None
    assert event.qos_by_load is not None
    assert set(event.qos_by_load) == {1.0, 1.5}
    # the reported winner is genuinely feasible under the scaled workload
    hot = PoolEvaluator(PROF, [FAST, SLOW], wl.scaled(1.5),
                        max_instances=MAX_INST)
    assert hot(event.new_best) >= 0.9
    assert event.qos_by_load[1.5] == hot(event.new_best)
    assert ev.n_evals > n_before


def test_rescale_legacy_callable_path_unchanged():
    space = SearchSpace(bounds=(5, 8), prices=(1.0, 0.3))

    def oracle(cfg, demand=31.0 * 1.5):
        return min(1.0, float(np.dot((10.0, 3.0),
                                     np.asarray(cfg, float))) / demand)

    opt = RibbonOptimizer(space, qos_target=0.99)
    for _ in range(20):
        cfg = opt.ask()
        if cfg is None or opt.done:
            break
        opt.tell(cfg, min(1.0, oracle(cfg) * 1.5))
    event = rescale(opt, oracle, budget=30)
    assert event.new_best is not None
    assert event.qos_by_load is None
    assert oracle(event.new_best) >= 0.99


def test_rescale_grid_requires_grid_evaluator():
    space = SearchSpace(bounds=(3, 3), prices=(1.0, 0.3))
    opt = RibbonOptimizer(space, qos_target=0.9)
    for _ in range(5):
        cfg = opt.ask()
        opt.tell(cfg, _oracle(cfg))
    with pytest.raises(TypeError):
        rescale(opt, _oracle, budget=5, load_factors=(1.0, 1.5))


# ------------------------------------------------------- edge cases + caches
def test_grid_edges_zero_pool_rows_and_single_query_no_nan():
    """Zero-pool config rows and single-query streams flow through the grid
    and waits paths without NaN."""
    wl = _workload(n=1, rate=50.0)
    sim = PoolSimulator(PROF, [FAST, SLOW], wl, max_instances=MAX_INST)
    cfgs = [(0, 0), (1, 0), (0, 2)]
    rates = sim.qos(cfgs, workloads=(1.0, 2.0)).rates
    assert rates.shape == (2, 3)
    assert not np.isnan(rates).any()
    assert (rates[:, 0] == 0.0).all()          # empty pool: all violations
    lat = sim.simulate(cfgs, workloads=(1.0, 2.0)).lat
    assert np.isinf(lat[:, 0]).all()
    assert np.isfinite(lat[:, 1:]).all()
    r1 = sim.simulate((1, 0))
    lat1, waits1 = r1.lat, r1.waits
    assert lat1.shape == waits1.shape == (1,)
    assert np.isfinite(lat1).all() and waits1[0] == 0.0
    # warm start over a single-query segment
    rw = sim.simulate((1, 0), state=sim.initial_state())
    np.testing.assert_array_equal(rw.lat, lat1)
    assert np.isfinite(rw.state.free[:1]).all()


def test_grid_arr_shard_cache_is_lru_with_hit_refresh():
    """The per-load-factor-tuple device cache of arrival grids evicts the
    least *recently used* entry: re-sweeping one level set keeps it resident
    while fresh sets cycle through."""
    sim = _sim()
    arr = np.asarray(sim.workload.arrivals, np.float32)[None, :]
    hot = ("b", 2, (1.0,))
    sim._grid_arr_shards(arr, "b", 2, (1.0,))
    for k in range(7):                          # fill the 8-entry cache
        sim._grid_arr_shards(arr, "b", 2, (1.0 + 0.1 * (k + 1),))
    assert hot in sim._grid_arrs and len(sim._grid_arrs) == 8
    sim._grid_arr_shards(arr, "b", 2, (1.0,))   # hit: refresh recency
    sim._grid_arr_shards(arr, "b", 2, (9.9,))   # miss: evicts the LRU entry
    assert hot in sim._grid_arrs                # survived thanks to the hit
    assert ("b", 2, (1.1,)) not in sim._grid_arrs   # the stalest went
    assert len(sim._grid_arrs) == 8


# ---------------------------------------------- pipelined multi-dispatch sweep
SWEEP_FLAVORS = ("cold", "warm", "routed")


def _sweep_case(flavor, n_pools):
    """A small candle evaluator, its sweep of ``n_pools`` pools x 3 loads
    for ``flavor``, the blocking ``qos`` arguments that score one chunk the
    same way, and the memo the sweep fills (as ``{(factor, pool): rate}``
    dicts in write order)."""
    ev, space, _ = make_paper_setup("candle", n_queries=150)
    # Pools of 36 down to 6 instances: the first chunk fills all 40 slots,
    # so its layouts and carries go to the device uncopied.
    pools = space.enumerate()[::-211][:n_pools]
    factors = (1.0, 1.25, 1.5)
    if flavor == "cold":
        def sweep():
            return ev.grid(pools, factors)

        def memo():
            return [{(1.0, k): v for k, v in ev._cache.items()},
                    ev._grid_cache]
        kw = {}
    elif flavor == "warm":
        deployed = (2, 3, 4)
        seg = ev.sim.segment_from(ev.sim.initial_state(), deployed)
        state = seg.state_at(90).rebased(float(ev.workload.arrivals[89]))

        def sweep():
            return ev.grid_from(state, pools, factors, deployed=deployed)

        def memo():
            (cache,) = ev._warm_cache.values()
            return [cache]
        kw = {"state": state, "deployed": deployed}
    else:
        policy = RoutingPolicy.cost_aware([t.price for t in ev.types])

        def sweep():
            return ev.grid(pools, factors, policy=policy)

        def memo():
            cold, grid = ev._policy_caches[policy.key()]
            return [{(1.0, k): v for k, v in cold.items()}, grid]
        kw = {"policy": policy}
    return ev, pools, factors, sweep, memo, kw


def _check_pipelined_sweep(flavor, n_pools, chunk=4):
    """The sweep, in chunks of ``chunk`` pools, against each chunk scored
    through blocking ``sim.qos`` on a fresh evaluator: the returned grid,
    every memo cell (and the order they were written in) and ``n_evals``
    bit for bit.  No host array the sweep hands to the device is written
    again before the sweep ends (on the CPU the device may read it in
    place, after a later dispatch was staged)."""
    ev, pools, factors, sweep, memo, kw = _sweep_case(flavor, n_pools)
    ref_ev, _, _, _, _, _ = _sweep_case(flavor, n_pools)
    blocks = []
    for i in range(0, len(pools), chunk):
        part = pools[i:i + chunk]
        n = len(part)
        width = 1 << (n - 1).bit_length()
        padded = np.concatenate([part, np.repeat(part[:1], width - n,
                                                 axis=0)])
        blocks.append(ref_ev.sim.qos(padded, workloads=factors,
                                     **kw).rates[:, :n])
    want = np.concatenate(blocks, axis=1)
    handed = []
    asarray = jnp.asarray

    def recording(x, *args, **kwargs):
        if isinstance(x, np.ndarray):
            handed.append((x, x.copy()))
        return asarray(x, *args, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PoolEvaluator, "_chunk", chunk)
        mp.setattr(jnp, "asarray", recording)
        got = sweep()
    assert handed
    for x, then in handed:
        np.testing.assert_array_equal(x, then)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == want.dtype
    assert ev.n_evals == len(factors) * len(pools)
    keys = [tuple(int(c) for c in p) for p in pools]
    written = [((f, k), float(want[w, b]))
               for i in range(0, len(keys), chunk)
               for w, f in enumerate(factors)
               for b, k in enumerate(keys[i:i + chunk], start=i)]
    dicts = memo()
    if len(dicts) == 2:
        expect = [[kv for kv in written if kv[0][0] == 1.0],
                  [kv for kv in written if kv[0][0] != 1.0]]
    else:
        expect = [written]
    assert [list(d.items()) for d in dicts] == expect


@pytest.mark.parametrize("n_pools", [10, 11])
@pytest.mark.parametrize("flavor", SWEEP_FLAVORS)
def test_pipelined_sweep_matches_blocking_chunks(flavor, n_pools):
    """Three dispatches a sweep (the last 2 pools wide, or 3 padded to 4):
    cold, warm and routed sweeps are bit-identical to blocking qos."""
    _check_pipelined_sweep(flavor, n_pools)


def test_pipelined_sweep_matches_blocking_chunks_on_four_devices():
    """The same sweeps on four forced host devices, where each dispatch
    runs the shard_map lane split (a 2-pool chunk pads a load level)."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    script = ("import sys\n"
              f"sys.path.insert(0, {str(root / 'tests')!r})\n"
              "import jax\n"
              "assert jax.local_device_count() == 4\n"
              "import test_grid_eval as t\n"
              "for flavor in t.SWEEP_FLAVORS:\n"
              "    for n_pools in (10, 11):\n"
              "        t._check_pipelined_sweep(flavor, n_pools)\n"
              "print('PIPELINED-OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=str(root))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PIPELINED-OK" in proc.stdout
