"""Routing policies as data (PR 7): policy-scan oracle equivalence, the
identity policy's bit-identity with ``policy=None`` on every lane, stacked
(policy x config) folding, input validation, the deprecation shims over the
unified ``simulate``/``qos`` surface, the joint pool x policy search space,
and the scenario engine's reroute action."""

import dataclasses
import inspect
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (JointSearchSpace, PruneSet, RibbonOptimizer,
                        SearchSpace, apply_prune_rules_joint)
from repro.serving import (NAMED_POLICIES, PoolEvaluator, PoolSimulator,
                           RoutingPolicy, named_policy)
from repro.serving import simulator as sim_mod
from repro.serving.autoscaler import rescale
from repro.serving.fault import (recover_from_capacity_change,
                                 recover_from_failure, reprice)
from repro.serving.instance import InstanceType, ModelProfile
from repro.serving.workload import generate_workload

FAST = InstanceType("fast", price=1.0, flops=1e9, mem_bw=1e9, overhead=1e-3)
SLOW = InstanceType("slow", price=0.3, flops=2e8, mem_bw=5e8, overhead=2e-3)
PROF = ModelProfile("toy", flops_per_sample=1e6, act_bytes_per_sample=1e4,
                    weight_bytes=1e5, qos_latency=0.05)


def _wl(seed=0, n=200, rate=120.0):
    return generate_workload(seed, n, rate, median_batch=8.0, max_batch=32)


def _sim(n=200, rate=120.0, seed=0):
    return PoolSimulator(PROF, [FAST, SLOW], _wl(seed, n, rate),
                        max_instances=8)


def _backlog_state(sim, deployed=(1, 1), upto=100):
    seg = sim.segment_from(sim.initial_state(), deployed)
    return seg.state_at(upto).rebased(float(sim.workload.arrivals[upto - 1]))


def python_policy_oracle(workload, types, counts, profile, policy):
    """Routed FCFS reference mirroring ``_simulate_scan_policy``: among
    idle slots minimize ``(type_pref + affinity*svc, slot)``; with none
    idle minimize ``(free + hedge*svc, slot)``.  Returns (lat, starts) so
    callers can also check schedule feasibility."""
    pref = np.asarray(policy.type_pref, dtype=np.float64)
    aff, hed = float(policy.affinity), float(policy.hedge)
    slots = [t for t, c in enumerate(counts) for _ in range(c)]
    free = [0.0] * len(slots)
    lat, starts = [], []
    for arr, b in zip(workload.arrivals, workload.batches):
        svc = [float(types[t].latency(profile, b)) for t in slots]
        idle = [s for s, f in enumerate(free) if f <= arr]
        if idle:
            pick = min(idle, key=lambda s: (pref[slots[s]] + aff * svc[s], s))
        else:
            pick = min(range(len(slots)),
                       key=lambda s: (free[s] + hed * svc[s], s))
        start = max(arr, free[pick])
        free[pick] = start + svc[pick]
        lat.append(free[pick] - arr)
        starts.append(start)
    return np.array(lat), np.array(starts)


POLICIES = [
    RoutingPolicy.fcfs(2),
    RoutingPolicy.cost_aware([1.0, 0.3]),
    RoutingPolicy.affine(2),
    RoutingPolicy.hedged(2),
    RoutingPolicy.from_order([1, 0], affinity=0.5, hedge=0.7, name="mix"),
]


# ------------------------------------------------------- oracle equivalence
@pytest.mark.parametrize("counts", [(1, 2), (3, 3), (2, 0)])
@pytest.mark.parametrize("policy", POLICIES, ids=lambda p: p.name)
def test_policy_scan_matches_python_oracle(counts, policy):
    wl = _wl()
    sim = PoolSimulator(PROF, [FAST, SLOW], wl, max_instances=8)
    got = sim.simulate(counts, policy=policy).lat
    want, _ = python_policy_oracle(wl, [FAST, SLOW], counts, PROF, policy)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@settings(max_examples=10)
@given(st.tuples(st.integers(0, 3), st.integers(1, 3)),
       st.floats(min_value=0.0, max_value=2.0),
       st.floats(min_value=0.0, max_value=1.0),
       st.integers(0, 1))
def test_policy_schedules_stay_feasible(counts, affinity, hedge, first):
    """Property sweep: any valid policy produces a feasible schedule (every
    query starts at or after its arrival, waits are the start delays) that
    matches the pure-python oracle."""
    policy = RoutingPolicy.from_order([first, 1 - first], affinity=affinity,
                                      hedge=hedge, name="prop")
    wl = _wl(seed=3, n=80, rate=250.0)
    sim = PoolSimulator(PROF, [FAST, SLOW], wl, max_instances=8)
    got = sim.simulate(counts, policy=policy)
    want, starts = python_policy_oracle(wl, [FAST, SLOW], counts, PROF,
                                        policy)
    assert (starts >= wl.arrivals - 1e-9).all()
    np.testing.assert_allclose(got.lat, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.waits, np.maximum(starts - wl.arrivals, 0),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------ identity policy == policy=None
def test_identity_policy_bit_identical_cold_lanes():
    sim = _sim()
    ident = RoutingPolicy.fcfs(2)
    cfg = (2, 1)
    cfgs = np.array([(1, 0), (2, 1), (0, 2), (3, 3)])
    base = sim.simulate(cfg)
    routed = sim.simulate(cfg, policy=ident)
    np.testing.assert_array_equal(base.lat, routed.lat)
    np.testing.assert_array_equal(base.waits, routed.waits)
    assert float(sim.qos(cfg).rates) == float(sim.qos(cfg,
                                                      policy=ident).rates)
    np.testing.assert_array_equal(sim.simulate(cfgs).lat,
                                  sim.simulate(cfgs, policy=ident).lat)
    np.testing.assert_array_equal(sim.qos(cfgs).rates,
                                  sim.qos(cfgs, policy=ident).rates)
    np.testing.assert_array_equal(
        sim.qos(cfgs, workloads=[1.0, 1.5]).rates,
        sim.qos(cfgs, workloads=[1.0, 1.5], policy=ident).rates)


def test_identity_policy_bit_identical_warm_lanes():
    sim = _sim()
    ident = RoutingPolicy.fcfs(2)
    state = _backlog_state(sim)
    cfgs = np.array([(2, 1), (1, 2)])
    base = sim.qos(cfgs, state=state, deployed=(1, 1))
    routed = sim.qos(cfgs, state=state, deployed=(1, 1), policy=ident)
    np.testing.assert_array_equal(base.rates, routed.rates)
    for sb, sr in zip(base.state, routed.state):
        np.testing.assert_array_equal(np.asarray(sb.free),
                                      np.asarray(sr.free))
    rw = sim.qos((1, 1), state=state)
    rwp = sim.qos((1, 1), state=state, policy=ident)
    assert rw.rates == rwp.rates
    np.testing.assert_array_equal(np.asarray(rw.state.free),
                                  np.asarray(rwp.state.free))


# --------------------------------------------------- stacked policy folding
def test_stacked_policy_rows_match_single_dispatches():
    sim = _sim(n=150)
    pols = [RoutingPolicy.fcfs(2), RoutingPolicy.cost_aware([1.0, 0.3]),
            RoutingPolicy.hedged(2)]
    stacked = RoutingPolicy.stack(pols)
    assert stacked.stacked and stacked.n_policies == 3
    cfgs = np.array([(1, 1), (2, 2), (0, 3)])
    joint = np.asarray(sim.qos(cfgs, policy=stacked).rates)
    assert joint.shape == (3, 3)
    lat = sim.simulate(cfgs, policy=stacked).lat
    assert lat.shape == (3, 3, sim.workload.n_queries)
    grid = np.asarray(sim.qos(cfgs, workloads=[1.0, 1.3],
                              policy=stacked).rates)
    assert grid.shape == (2, 3, 3)
    for p, pol in enumerate(pols):
        np.testing.assert_array_equal(joint[p],
                                      sim.qos(cfgs, policy=pol).rates)
        np.testing.assert_array_equal(
            grid[:, p],
            sim.qos(cfgs, workloads=[1.0, 1.3], policy=pol).rates)
        np.testing.assert_array_equal(lat[p],
                                      sim.simulate(cfgs, policy=pol).lat)


def test_stacked_policy_warm_lanes_match_single_dispatches():
    sim = _sim()
    state = _backlog_state(sim)
    pols = [RoutingPolicy.fcfs(2), RoutingPolicy.hedged(2)]
    stacked = RoutingPolicy.stack(pols)
    cfgs = np.array([(2, 1), (1, 2), (2, 2)])
    r = sim.qos(cfgs, state=state, deployed=(1, 1), policy=stacked)
    rates = np.asarray(r.rates)
    assert rates.shape == (2, 3)
    assert len(r.state) == 2 and len(r.state[0]) == 3
    for p, pol in enumerate(pols):
        ref = sim.qos(cfgs, state=state, deployed=(1, 1), policy=pol)
        np.testing.assert_array_equal(rates[p], ref.rates)
        for sb, sr in zip(ref.state, r.state[p]):
            np.testing.assert_array_equal(np.asarray(sb.free),
                                          np.asarray(sr.free))


# ------------------------------------------------------------- validation
def test_policy_validation_errors():
    with pytest.raises(ValueError, match="permutation"):
        RoutingPolicy.from_order([0, 0])
    with pytest.raises(ValueError, match="outside"):
        RoutingPolicy.from_order([0, 2])
    with pytest.raises(ValueError, match="hedge"):
        RoutingPolicy.hedged(2, hedge=1.5)
    with pytest.raises(ValueError, match="affinity"):
        RoutingPolicy.affine(2, affinity=-1.0)
    with pytest.raises(ValueError, match="finite"):
        RoutingPolicy(type_pref=np.array([np.nan, 0.0]))
    with pytest.raises(ValueError, match="does not match the policy axis"):
        RoutingPolicy(type_pref=np.zeros((2, 2)))
    with pytest.raises(ValueError, match="stack takes unstacked"):
        RoutingPolicy.stack([RoutingPolicy.stack([RoutingPolicy.fcfs(2)])])
    with pytest.raises(ValueError, match="unknown routing policy"):
        named_policy("nope", [1.0, 0.5])
    for name in NAMED_POLICIES:
        assert named_policy(name, [1.0, 0.5]).n_types == 2


def test_simulator_rejects_bad_policy_inputs():
    sim = _sim(n=40)
    with pytest.raises(ValueError, match="routes over 3 instance"):
        sim.qos((1, 1), policy=RoutingPolicy.fcfs(3))
    with pytest.raises(TypeError, match="RoutingPolicy"):
        sim.qos((1, 1), policy="hedged")
    stacked = RoutingPolicy.stack([RoutingPolicy.fcfs(2),
                                   RoutingPolicy.hedged(2)])
    with pytest.raises(ValueError, match="stacked policy needs a config"):
        sim.qos((1, 1), policy=stacked)
    with pytest.raises(ValueError, match="require state="):
        sim.qos(np.array([(1, 1)]), deployed=(1, 1))


def test_control_plane_keyword_only_vocabulary():
    """The PR 7 control-plane vocabulary is keyword-only everywhere."""
    for fn, kws in [
        (rescale, ("budget", "warm_state", "deployed", "now", "policy")),
        (recover_from_capacity_change, ("budget", "policy")),
        (recover_from_failure, ("failed_type", "budget", "policy")),
        (reprice, ("budget", "policy")),
    ]:
        sig = inspect.signature(fn)
        for kw in kws:
            assert sig.parameters[kw].kind is inspect.Parameter.KEYWORD_ONLY, \
                f"{fn.__name__}({kw}=) must be keyword-only"


# ------------------------------------------------------- deprecation shims
def _shim_cases(sim, state, deployed):
    cfg = (1, 1)
    cfgs = np.array([(1, 1), (2, 0)])
    factors = [1.0, 1.2]

    def pair(r):
        return r.lat, r.state

    return {
        "latencies": (lambda: sim.latencies(cfg),
                      lambda: sim.simulate(cfg).lat),
        "latencies_waits": (lambda: sim.latencies_waits(cfg),
                            lambda: (lambda r: (r.lat, r.waits))(
                                sim.simulate(cfg))),
        "qos_rate": (lambda: sim.qos_rate(cfg),
                     lambda: float(sim.qos(cfg).rates)),
        "latencies_from": (lambda: sim.latencies_from(state, cfg),
                           lambda: pair(sim.simulate(cfg, state=state))),
        "latencies_waits_from": (
            lambda: sim.latencies_waits_from(state, cfg),
            lambda: (lambda r: (r.lat, r.waits, r.state))(
                sim.simulate(cfg, state=state))),
        "qos_rate_from": (lambda: sim.qos_rate_from(state, cfg),
                          lambda: (lambda r: (r.rates, r.state))(
                              sim.qos(cfg, state=state))),
        "latencies_batch": (lambda: sim.latencies_batch(cfgs),
                            lambda: sim.simulate(cfgs).lat),
        "qos_rate_batch": (lambda: sim.qos_rate_batch(cfgs),
                           lambda: sim.qos(cfgs).rates),
        "latencies_batch_from": (
            lambda: sim.latencies_batch_from(state, cfgs, deployed=deployed),
            lambda: pair(sim.simulate(cfgs, state=state,
                                      deployed=deployed))),
        "qos_rate_batch_from": (
            lambda: sim.qos_rate_batch_from(state, cfgs, deployed=deployed),
            lambda: (lambda r: (r.rates, r.state))(
                sim.qos(cfgs, state=state, deployed=deployed))),
        "latencies_grid": (lambda: sim.latencies_grid(cfgs, factors),
                           lambda: sim.simulate(cfgs,
                                                workloads=factors).lat),
        "qos_rate_grid": (lambda: sim.qos_rate_grid(cfgs, factors),
                          lambda: sim.qos(cfgs, workloads=factors).rates),
        "latencies_grid_from": (
            lambda: sim.latencies_grid_from(state, cfgs, factors,
                                            deployed=deployed),
            lambda: sim.simulate(cfgs, workloads=factors, state=state,
                                 deployed=deployed).lat),
        "qos_rate_grid_from": (
            lambda: sim.qos_rate_grid_from(state, cfgs, factors,
                                           deployed=deployed),
            lambda: sim.qos(cfgs, workloads=factors, state=state,
                            deployed=deployed).rates),
    }


def _flat_equal(old, new):
    """Bitwise equality over possibly-nested (array, state, list) returns."""
    if isinstance(old, tuple):
        assert isinstance(new, tuple) and len(old) == len(new)
        for o, n in zip(old, new):
            _flat_equal(o, n)
    elif isinstance(old, list):
        for o, n in zip(old, new):
            _flat_equal(o, n)
    elif hasattr(old, "free"):          # PoolState carries
        np.testing.assert_array_equal(np.asarray(old.free),
                                      np.asarray(new.free))
    else:
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_every_shim_warns_once_and_delegates():
    sim = _sim(n=60)
    state = _backlog_state(sim, deployed=(1, 1), upto=30)
    cases = _shim_cases(sim, state, (1, 1))
    assert len(cases) == 14
    for name, (shim, new_api) in cases.items():
        sim_mod._WARNED.discard(name)
        with pytest.warns(DeprecationWarning,
                          match=rf"PoolSimulator\.{name}\(\) is deprecated"):
            old = shim()
        _flat_equal(old, new_api())
        # Second call: the warning fired once per name and stays quiet.
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            shim()


def test_shim_warning_names_migration_doc():
    sim = _sim(n=40)
    sim_mod._WARNED.discard("qos_rate")
    with pytest.warns(DeprecationWarning,
                      match=r"docs/api_migration\.md"):
        sim.qos_rate((1, 1))


# ----------------------------------------------- joint pool x policy space
def test_joint_space_shape_and_validation():
    base = SearchSpace(bounds=(2, 2), prices=(1.0, 0.4))
    js = JointSearchSpace.joint(base, 3)
    assert js.bounds == (2, 2, 2)
    assert js.prices[-1] == 0.0 and js.n_policies == 3
    assert js.size == base.size * 3
    # the router is free: cost is independent of the policy coordinate
    lattice = js.enumerate()
    costs = js.costs(lattice)
    assert float(costs[js.index_of((2, 1, 0))]) == float(
        costs[js.index_of((2, 1, 2))])
    with pytest.raises(ValueError, match="policy axis"):
        JointSearchSpace(bounds=(2, 2), prices=(1.0, 0.0), n_policies=2)
    with pytest.raises(ValueError, match="free"):
        JointSearchSpace(bounds=(2, 1), prices=(1.0, 0.5), n_policies=2)


def test_joint_prune_mirrors_restrict_down_set_to_same_policy():
    import jax.numpy as jnp

    js = JointSearchSpace.joint(SearchSpace(bounds=(2, 2),
                                            prices=(1.0, 0.4)), 2)
    lattice = js.enumerate()
    costs = js.costs(lattice)
    cfg = (1, 1, 1)
    ps = PruneSet(js)
    ps.prune_down_set(cfg)
    pruned = lattice[ps.mask]
    assert len(pruned) > 0
    # the categorical policy axis is never crossed by capacity dominance
    assert (pruned[:, -1] == 1).all()
    blocked = apply_prune_rules_joint(
        jnp.zeros(js.size, dtype=bool), jnp.asarray(lattice),
        jnp.asarray(costs), js.index_of(cfg),
        jnp.asarray(cfg, dtype=jnp.int32), jnp.inf, True, False)
    np.testing.assert_array_equal(np.asarray(blocked), ps.mask)


def test_joint_optimizer_searches_pool_and_policy_together():
    """BO over the joint lattice: the policy coordinate selects the memoized
    per-policy evaluator lane, and the search converges on a feasible
    (pool, policy) point."""
    wl = _wl(n=150, rate=150.0)
    ev = PoolEvaluator(PROF, [FAST, SLOW], wl)
    pols = [named_policy(n, [t.price for t in ev.types])
            for n in NAMED_POLICIES]
    space = JointSearchSpace.joint(SearchSpace(bounds=(3, 3),
                                               prices=(1.0, 0.3)),
                                   len(pols))
    opt = RibbonOptimizer(space, qos_target=0.9, start=(1, 1, 0))
    for _ in range(40):
        if opt.done:
            break
        cfg = opt.ask()
        if cfg is None:
            break
        opt.tell(cfg, ev(tuple(cfg[:-1]), policy=pols[cfg[-1]]))
    best = opt.trace.best_feasible()
    assert best is not None
    pool, pol_idx = tuple(best.config[:-1]), int(best.config[-1])
    assert ev(pool, policy=pols[pol_idx]) >= 0.9
    # quoted cost ignores the free policy coordinate
    assert best.cost == pytest.approx(
        float(np.dot(pool, (1.0, 0.3))))


def test_evaluator_memoizes_per_policy():
    ev = PoolEvaluator(PROF, [FAST, SLOW], _wl(n=80))
    fcfs, hedged = RoutingPolicy.fcfs(2), RoutingPolicy.hedged(2)
    assert ev((1, 1)) == ev((1, 1), policy=None)
    assert ev((1, 1), policy=fcfs) == ev((1, 1))   # identity policy
    ev((2, 1), policy=hedged)
    assert hedged.key() in ev._policy_caches
    with pytest.raises(ValueError, match="stacked"):
        ev((1, 1), policy=RoutingPolicy.stack([fcfs, hedged]))


# ----------------------------------------------------- scenario integration
def test_spec_rejects_unknown_route_policy():
    from repro.scenario.registry import flash_crowd

    spec = flash_crowd(n=60, window=30, routed=True)
    assert spec.route_policies == NAMED_POLICIES
    bad = dataclasses.replace(spec, route_policies=("fcfs", "bogus"))
    with pytest.raises(ValueError, match="unknown routing policy 'bogus'"):
        bad.validate()


@pytest.mark.slow
def test_engine_reroute_absorbs_flash_crowd_cheaper_than_fcfs():
    """On the heterogeneous paper pool the routed engine absorbs the 1.6x
    surge by switching the router (0 BO evaluations) instead of buying
    hardware, and finishes the episode cheaper than the FCFS-only engine at
    the same QoS target."""
    from repro.scenario import ScenarioEngine, paper_simulator_plane
    from repro.scenario.registry import flash_crowd

    # The seed picks an episode whose surge the hedged router alone absorbs
    # while FCFS must buy hardware; it is tied to the workload's threefry
    # bits (partitionable threefry, JAX >= 0.5), not true of every seed.
    reports = {}
    for routed in (True, False):
        spec = flash_crowd(n=360, window=60, seed=14, routed=routed)
        spec = dataclasses.replace(spec, init_budget=4, qos_target=0.98)
        plane, space = paper_simulator_plane("mtwnd", spec)
        reports[routed] = ScenarioEngine(spec, plane, space,
                                         start=(4, 1, 1)).run()
    routed_rep, legacy_rep = reports[True], reports[False]
    reroutes = [a for a in routed_rep.actions if a.kind == "reroute"]
    assert len(reroutes) == 1
    assert reroutes[0].policy == "hedged"
    assert reroutes[0].bo_evals == 0
    assert reroutes[0].old_config == reroutes[0].new_config
    assert not any(a.kind == "reroute" for a in legacy_rep.actions)
    assert routed_rep.recovered_all_events
    assert routed_rep.qos_rate >= spec.qos_target
    assert legacy_rep.qos_rate >= spec.qos_target
    # same QoS target met, strictly less money and less search
    assert routed_rep.total_cost < legacy_rep.total_cost
    assert routed_rep.bo_evals < legacy_rep.bo_evals
