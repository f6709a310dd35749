"""Pool evaluation glue: QoS oracle + cost metrics for the search strategies.

``PoolEvaluator`` is the black-box f(x) the paper's BO samples: it deploys a
pool configuration against the query stream (simulation plane) and returns the
measured QoS satisfaction rate.  Results are memoized — the physical analogue
is that an already-profiled configuration need not be re-deployed.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import ClassVar

import numpy as np

from .. import tracing
from ..core.search_space import SearchSpace
from .instance import (AWS_INSTANCES, MODEL_PROFILES, PAPER_POOLS,
                       InstanceType, ModelProfile)
from .routing import RoutingPolicy
from .simulator import PoolSimulator
from .workload import BucketedWorkloadSpec, Workload, WorkloadSpec


def cost_effectiveness(perf_qps: float, price_per_hour: float) -> float:
    """Paper Eq. 1: 3600 * Perf / Price  (queries per dollar)."""
    return 3600.0 * perf_qps / price_per_hour


@dataclass
class PoolEvaluator:
    """QoS oracle over a fixed (model, type order, workload)."""

    model: ModelProfile
    types: list[InstanceType]
    workload: Workload
    max_instances: int = 40
    n_evals: int = field(default=0, init=False)

    # Uncached configs are simulated in vmapped chunks padded to powers of
    # two (1, 2, ..., _chunk): at most log2(_chunk)+1 compiled executables,
    # and small batches waste < 2x padding instead of simulating a full
    # fixed-size chunk.
    _chunk: ClassVar[int] = 64
    # Warm-keyed memo bound: per-cell caches are kept for this many distinct
    # (state, deployed, now) warm keys, LRU — an adaptation re-sweeping its
    # monitored levels from one cut hits the memo, while long-gone cuts
    # (every adaptation carries a fresh backlog) age out.
    _warm_states: ClassVar[int] = 4

    def __post_init__(self):
        self.sim = PoolSimulator(self.model, self.types, self.workload,
                                 max_instances=self.max_instances)
        # The evaluator's spans and its simulator's share one request id.
        self.request = self.sim.request
        self._cache: dict[tuple[int, ...], float] = {}
        # (load_factor, config) -> rate for factors != 1.0; the unit factor
        # shares self._cache so grid sweeps and plain calls see one memo.
        self._grid_cache: dict[tuple[float, tuple[int, ...]], float] = {}
        # warm key -> {(load_factor, config) -> rate}; see grid_from.
        self._warm_cache: dict[tuple, dict] = {}
        # RoutingPolicy.key() -> (cold cache, grid cache): each policy gets
        # its own memo pair — the legacy pair above stays the policy=None
        # view, so FCFS callers keep bit-identical memo behavior.
        self._policy_caches: dict[tuple, tuple[dict, dict]] = {}

    @staticmethod
    def _policy_key(policy: RoutingPolicy | None):
        if policy is None:
            return None
        if policy.stacked:
            raise ValueError(
                "PoolEvaluator memoizes per single policy; score stacked "
                "policies through PoolSimulator.qos or pass policy.row(p)")
        return policy.key()

    def _caches_for(self, pk) -> tuple[dict, dict]:
        if pk is None:
            return self._cache, self._grid_cache
        return self._policy_caches.setdefault(pk, ({}, {}))

    def __call__(self, config, *, policy=None) -> float:
        key = tuple(int(c) for c in config)
        cache, _ = self._caches_for(self._policy_key(policy))
        if key not in cache:
            with tracing.span("pool.eval", self.request):
                cache[key] = float(self.sim.qos(key, policy=policy).rates)
            self.n_evals += 1
        return cache[key]

    def _cell_get(self, factor: float, key: tuple[int, ...]):
        if factor == 1.0:
            return self._cache.get(key)
        return self._grid_cache.get((factor, key))

    def _cell_put(self, factor: float, key: tuple[int, ...], rate: float):
        if factor == 1.0:
            self._cache[key] = rate
        else:
            self._grid_cache[(factor, key)] = rate

    def _pow2_chunks(self, arr: np.ndarray):
        """Yield (padded_chunk, start, n) pieces of ``arr``: ``_chunk``-
        bounded slices padded to the next power of two with repeats of their
        first row, so small batches share a handful of compiled executables
        (both ``batch`` and ``grid`` dispatch through this policy)."""
        for i in range(0, len(arr), self._chunk):
            chunk = arr[i:i + self._chunk]
            n = len(chunk)
            width = 1 << (n - 1).bit_length()   # next power of two
            if width > n:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[:1], width - n, axis=0)])
            yield chunk, i, n

    def batch(self, configs, *, policy=None) -> np.ndarray:
        """QoS rates for many configs via the batched simulator.

        Deduplicates against the memo cache (``policy=`` selects that
        policy's own memo pair), evaluates only the misses (padded to
        ``_chunk``-sized dispatches so the executable is compiled once), and
        returns rates aligned with ``configs``.
        """
        keys = [tuple(int(c) for c in cfg) for cfg in configs]
        cache, _ = self._caches_for(self._policy_key(policy))
        missing = [k for k in dict.fromkeys(keys) if k not in cache]
        if missing:
            with tracing.span("pool.eval", self.request):
                rates = []
                for chunk, _, n in self._pow2_chunks(
                        np.asarray(missing, dtype=np.int64)):
                    rates.append(self.sim.qos(chunk, policy=policy).rates[:n])
                rates = np.concatenate(rates)
                for k, r in zip(missing, rates):
                    cache[k] = float(r)
            self.n_evals += len(missing)
        return np.asarray([cache[k] for k in keys], dtype=np.float64)

    def grid(self, configs, load_factors, *, policy=None) -> np.ndarray:
        """QoS rates on the (load level × config) grid, one sweep.

        ``load_factors`` scale the bound workload (``Workload.scaled``
        semantics: factor 1.5 = 1.5x heavier traffic).  Returns (W, B)
        float64 aligned with the inputs; cell ``[w, b]`` equals what a
        ``PoolEvaluator`` bound to ``workload.scaled(load_factors[w])``
        would measure for ``configs[b]``.

        Memoized per (load factor, config) cell.  Misses are evaluated as a
        cross product — every load level with any miss × every config missing
        somewhere — in ``_chunk``-bounded grid dispatches, so a rescale
        loop's incumbent + candidates × monitored levels costs one device
        round-trip.  ``policy=`` routes dispatch and selects that policy's
        memo pair.  ``n_evals`` counts newly simulated cells only.
        """
        pk = self._policy_key(policy)
        if pk is None:
            cell_get, cell_put = self._cell_get, self._cell_put
        else:
            cache, grid_cache = self._caches_for(pk)

            def cell_get(f, k):
                return cache.get(k) if f == 1.0 else grid_cache.get((f, k))

            def cell_put(f, k, rate):
                if f == 1.0:
                    cache[k] = rate
                else:
                    grid_cache[(f, k)] = rate
        return self._sweep_grid(
            configs, load_factors, cell_get, cell_put,
            lambda chunk, rows: self.sim._qos_grid_issue(chunk, rows,
                                                         policy=policy))

    def _sweep_grid(self, configs, load_factors, cell_get, cell_put,
                    issue) -> np.ndarray:
        """Shared memoized (load level × config) sweep behind ``grid`` and
        ``grid_from``: misses are evaluated as a cross product — every load
        level with any miss × every config missing somewhere — in
        ``_chunk``-bounded ``issue(chunk, rows)`` grid dispatches, so one
        rescale round costs one device round-trip whichever memo backs it.

        The dispatches are disjoint column blocks of one grid, so every one
        is issued before the first is fetched: the device scans the queued
        ones back to back while the host stages the next.  They are then
        fetched in order, each followed by its memo writes.  ``n_evals``
        counts newly simulated cells only."""
        with tracing.span("pool.memo", self.request):
            keys = [tuple(int(c) for c in cfg) for cfg in configs]
            factors = [float(f) for f in load_factors]
            uniq_keys = list(dict.fromkeys(keys))
            uniq_factors = list(dict.fromkeys(factors))
            missing = {(f, k) for f in uniq_factors for k in uniq_keys
                       if cell_get(f, k) is None}
            cols = [k for k in uniq_keys if any((f, k) in missing
                                                for f in uniq_factors)]
            rows = [f for f in uniq_factors if any((f, k) in missing
                                                   for k in cols)]
            chunks = list(self._pow2_chunks(np.asarray(cols, dtype=np.int64)))
        pending = [issue(chunk, rows) for chunk, _, _ in chunks]
        for j, (_, i, n) in enumerate(chunks):
            rates = self.sim._qos_grid_fetch(
                pending[j], queued=len(chunks) - 1 - j)[:, :n]
            with tracing.span("pool.memo", self.request):
                for w, f in enumerate(rows):
                    for b, k in enumerate(cols[i:i + self._chunk]):
                        cell_put(f, k, float(rates[w, b]))
        self.n_evals += len(missing)
        with tracing.span("pool.memo", self.request):
            return np.asarray([[cell_get(f, k) for k in keys]
                               for f in factors], dtype=np.float64)

    def grid_from(self, state, configs, load_factors, *, deployed=None,
                  now=None, warmup=None, policy=None) -> np.ndarray:
        """Warm-start ``grid``: QoS rates of candidate pools scored from a
        live carry (each candidate's initial state is the ``PoolState.remap``
        of the currently ``deployed`` pool — what-if adaptation under the
        current queue, slots added by the switch paying their tier's
        ``warmup`` cold start).  Cell ``[w, b]`` equals the warm
        single-config ``qos`` lane on the scaled workload bound to that
        candidate's remapped state, exactly.

        Memoized per (warm state, load factor, config) cell: a rescale round
        re-sweeping its monitored levels from one adaptation cut costs one
        device dispatch, and the per-state caches are LRU-bounded
        (``_warm_states``) because every cut carries a fresh backlog — warm
        cells, unlike the cold memo, go stale with their cut.  ``n_evals``
        counts newly simulated cells only.
        """
        warm_key = (
            None if deployed is None else tuple(int(c) for c in deployed),
            None if now is None else float(now),
            None if warmup is None else tuple(float(w) for w in warmup),
            float(state.clock),
            tuple(np.asarray(state.free, dtype=np.float64).tolist()),
            self._policy_key(policy),
        )
        cache = self._warm_cache.pop(warm_key, None)
        if cache is None:
            cache = {}
            while len(self._warm_cache) >= self._warm_states:
                self._warm_cache.pop(next(iter(self._warm_cache)))
        # (Re-)inserting moves the key to the recent end of the dict.
        self._warm_cache[warm_key] = cache
        return self._sweep_grid(
            configs, load_factors,
            lambda f, k: cache.get((f, k)),
            lambda f, k, rate: cache.__setitem__((f, k), rate),
            lambda chunk, rows: self.sim._qos_grid_issue(
                chunk, rows, state=state, deployed=deployed, now=now,
                warmup=warmup, policy=policy))

    def exhaustive(self, space: SearchSpace, qos_target: float,
                   load_factor: float = 1.0, *, policy=None):
        """Ground-truth optimum + total exhaustive cost (paper Fig. 13
        normalizer), swept through the batched simulator in one pass —
        or, for ``load_factor != 1``, through a one-row grid sweep of the
        scaled workload (shared memo, no second evaluator).
        Returns (best_config, best_cost, exhaustive_cost)."""
        lattice = space.enumerate()
        costs = space.costs(lattice)
        if load_factor == 1.0:
            rates = self.batch(lattice, policy=policy)
        else:
            rates = self.grid(lattice, [load_factor], policy=policy)[0]
        total = float(costs.sum())
        feasible = rates >= qos_target
        if not feasible.any():
            return None, np.inf, total
        i = int(np.argmin(np.where(feasible, costs, np.inf)))
        return tuple(int(c) for c in lattice[i]), float(costs[i]), total


def best_homogeneous(evaluator: PoolEvaluator, type_index: int, prices,
                     qos_target: float, cap: int = 24, *, policy=None):
    """Minimum-count homogeneous pool of one type meeting QoS, evaluated as
    one batched sweep over counts 1..cap.  Returns (count, cost) or
    (None, inf).

    ``policy=`` scores the pool under that routing policy (the evaluator's
    per-policy memo pair), so homogeneous baselines compare apples to apples
    against routed diverse pools — a single-type pool still behaves
    differently under size-aware dispatch than under FCFS when the policy
    reorders its queue."""
    n = len(evaluator.types)
    cfgs = np.zeros((cap, n), dtype=np.int64)
    cfgs[:, type_index] = np.arange(1, cap + 1)
    rates = evaluator.batch(cfgs, policy=policy)
    ok = np.nonzero(rates >= qos_target)[0]
    if ok.size == 0:
        return None, np.inf
    count = int(ok[0]) + 1
    return count, count * prices[type_index]


# Request-size mixes backing the bucketed batch distributions: weights[i][j]
# is the traffic fraction landing in (input-size bucket i, output-size bucket
# j); the scales multiply the roofline profile's per-sample bytes (input axis)
# and flops (output axis).  "small" skews toward short requests, "large"
# toward long ones — the drifting pair the dist-drift-bucketed scenario uses.
BUCKET_DIST_MIXES: dict[str, dict] = {
    "bucketed-small": {"weights": ((0.45, 0.15), (0.30, 0.10)),
                       "input_scales": (0.7, 1.6),
                       "output_scales": (0.8, 1.5)},
    "bucketed-large": {"weights": ((0.10, 0.30), (0.15, 0.45)),
                       "input_scales": (0.7, 1.6),
                       "output_scales": (0.8, 1.5)},
}


def paper_spec(model_name: str, seed: int = 0,
               rate_qps: float | None = None,
               batch_dist: str = "lognormal") -> WorkloadSpec:
    """The standard per-model stream as an on-device :class:`WorkloadSpec`
    (paper §5.1 parameters); ``realize()`` of this spec IS the canonical
    stream every lane scores."""
    profile = MODEL_PROFILES[model_name]
    if rate_qps is None:
        rate_qps = DEFAULT_RATES[model_name]
    return WorkloadSpec(seed=seed, rate_qps=rate_qps, batch_dist=batch_dist,
                        median_batch=profile.median_batch,
                        mean_batch=2.0 * profile.median_batch,
                        std_batch=profile.median_batch,
                        max_batch=profile.max_batch)


def paper_bucketed_spec(model_name: str, batch_dist: str, seed: int = 0,
                        rate_qps: float | None = None) -> BucketedWorkloadSpec:
    """Bucketed variant of the standard per-model stream: the named mix from
    ``BUCKET_DIST_MIXES`` layered over the lognormal base — same seed, same
    arrival and batch bits, only the bucket annotation added."""
    mix = BUCKET_DIST_MIXES[batch_dist]
    if rate_qps is None:
        rate_qps = DEFAULT_RATES[model_name]
    base = paper_spec(model_name, seed=seed, rate_qps=rate_qps,
                      batch_dist="lognormal")
    rates = tuple(tuple(w * float(rate_qps) for w in row)
                  for row in mix["weights"])
    return BucketedWorkloadSpec(base=base, rates=rates,
                                input_scales=mix["input_scales"],
                                output_scales=mix["output_scales"])


def paper_workload(model_name: str, seed: int = 0, n_queries: int = 1500,
                   rate_qps: float | None = None,
                   batch_dist: str = "lognormal") -> Workload:
    """The standard per-model query stream (paper §5.1 parameters).

    Streams that differ only in ``batch_dist`` share the same arrival times
    (one seed/rate = one arrival stream, whatever the batch or bucket law),
    which is what lets the stacked service-table grid axis sweep all
    distributions over one arrival grid (paper Fig. 11, scenario dist-drift
    phases).  Bucketed dist names (``BUCKET_DIST_MIXES``) return the same
    lognormal stream with a per-query bucket annotation layered on."""
    if batch_dist in BUCKET_DIST_MIXES:
        return paper_bucketed_spec(model_name, batch_dist, seed=seed,
                                   rate_qps=rate_qps).realize(n_queries)
    return paper_spec(model_name, seed=seed, rate_qps=rate_qps,
                      batch_dist=batch_dist).realize(n_queries)


def make_paper_setup(model_name: str, seed: int = 0, n_queries: int = 1500,
                     rate_qps: float | None = None,
                     batch_dist: str = "lognormal"):
    """Standard experimental setup for one of the paper's five models:
    returns (evaluator, space, model_profile) with the Table 3 diverse pool.

    Arrival rates are chosen per model so that the optimal homogeneous pool
    needs ~4-8 instances (the regime of paper Fig. 4).
    """
    profile = MODEL_PROFILES[model_name]
    pool_names = PAPER_POOLS[model_name]["diverse"]
    types = [AWS_INSTANCES[n] for n in pool_names]
    wl = paper_workload(model_name, seed=seed, n_queries=n_queries,
                        rate_qps=rate_qps, batch_dist=batch_dist)
    evaluator = PoolEvaluator(profile, types, wl)
    prices = tuple(t.price for t in types)
    bounds = DEFAULT_BOUNDS[model_name]
    space = SearchSpace(bounds=bounds, prices=prices)
    return evaluator, space, profile


# Arrival rates giving paper-like pool sizes (validated by bench_pool_example).
DEFAULT_RATES: dict[str, float] = {
    "mtwnd": 800.0,
    "dien": 850.0,
    "candle": 550.0,
    "resnet50": 275.0,
    "vgg19": 36.0,
}

# Per-type search bounds m_i (paper: count at which QoS rate saturates).
DEFAULT_BOUNDS: dict[str, tuple[int, ...]] = {
    "mtwnd": (8, 10, 12),
    "dien": (8, 10, 12),
    "candle": (10, 12, 14),
    "resnet50": (10, 12, 14),
    "vgg19": (10, 12, 14),
}
