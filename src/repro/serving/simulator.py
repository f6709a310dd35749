"""Batched, device-resident FCFS queueing simulator over heterogeneous pools.

Implements the paper's serving discipline (§5.1): "query processing follows a
simple first-come-first-serve (FCFS) manner, with the first arrived query
going to the first available instance following the heterogeneous type order
... multiple queries are served concurrently by the available pool".

Dispatch rule per query (in arrival order):
  * if one or more instances are idle at the arrival instant, take the first
    idle instance in pool type order;
  * otherwise wait for the earliest-freeing instance (head-of-line FCFS).

Architecture (the batched evaluation engine):

  * the core is a ``jax.lax.scan`` over the query stream with per-instance
    next-free times as carry, padded to ``max_instances`` slots so one
    executable serves every pool configuration;
  * the scan is **vmapped over a batch axis of slot layouts**: a single
    compiled executable evaluates ``B`` pool configurations in one device
    dispatch (the batched lane of ``simulate``/``qos``, selected by a
    ``(B, n_types)`` config array).  The arrival stream and the
    (n_types, n_queries) service table are shared across the batch —
    only the (B, max_instances) slot layout varies;
  * a second **workload axis** joins the batch axis for load-level sweeps
    (the ``workloads=`` grid lane): one dispatch simulates ``W`` scaled
    arrival streams × ``B`` configs.  The grid ``qos`` lane runs a
    leaner fused executable — QoS counting folded into the scan carry, slot
    padding trimmed to the batch's occupancy, and the flattened ``W·B`` lane
    axis sharded across XLA host devices when more than one is configured
    (``--xla_force_host_platform_device_count``, see benchmarks/__init__.py);
  * config→slot expansion is fully vectorized (cumulative-count searchsorted,
    no per-slot Python loops) so host-side prep is O(B·max_instances) numpy;
  * the service table is memoized per (model, types, batches) — see
    ``instance.service_time_table``.  ``Workload.scaled`` keeps the batch
    stream, so every load level of a grid shares one table.

The BO loop evaluates hundreds of configurations — this batched path is the
hot path of the *search*, exactly the paper's "costly evaluation" being
amortized.  The single-config lane is kept as the q=1 special case and
agrees bit-for-bit with row ``i`` of the batched result, and
cell ``[w, b]`` of the grid agrees bit-for-bit with the single path bound to
``workload.scaled(load_factors[w])`` (tests/test_batch_eval.py,
tests/test_grid_eval.py).

Continuous-time warm starts (the scenario engine's episode clock): a
:class:`PoolState` carries per-slot next-free times (episode time) plus a
``clock`` offset mapping the bound stream's local ``t=0`` into episode time.
Passing ``state=`` to ``simulate``/``qos`` starts the scan from that carry
and returns the final carry, so a stream served in consecutive segments
(each segment's final state feeding the next) produces the *same bits* as
one whole-stream call — ``initial_state()`` (idle pool at clock 0) is the
identity element: ``simulate(c, state=initial_state())`` equals
``simulate(c)`` bit for bit.  ``PoolState.remap`` threads the carry
through a pool reconfiguration (surviving instances keep their in-flight
work, removed slots drop it, added slots start idle), and ``segment_from``
exposes the per-prefix carry the scenario engine needs when it rolls a
segment back to an adaptation cut (tests/test_simulator.py,
tests/test_scenario.py).

Warm starts ride the batched and grid lanes too: ``state=`` composes with
the batch and ``workloads=`` axes (plus ``deployed=``/``now=``/``warmup=``)
to evaluate B *candidate* pools from one live carry in a single dispatch —
each candidate's initial carry is a vectorized ``PoolState.remap_batch`` of
the deployed pool's state (what-if adaptation under the current queue, not
from idle).  Every cell stays bit-identical to the sequential warm
single-config path on that candidate's remapped state, and the idle carry
at clock 0 reproduces the cold batched/grid paths bit for bit
(tests/test_warm_lanes.py).

Unified surface (PR 7): every lane above is reached through one pair of
entry points — ``PoolSimulator.simulate(configs, *, state=, workloads=,
service_tables=, policy=, deployed=, now=, warmup=)`` returning a
:class:`SimResult` and the lean ``qos(...)`` returning a
:class:`QosResult` — with the legacy ``latencies*``/``qos_rate*`` names
kept as deprecation shims that delegate and warn once per name
(docs/api_migration.md maps every old call).  The dispatch rule itself is
*data*: ``policy=`` takes a :class:`~repro.serving.routing.RoutingPolicy`
(cost-aware preference order, per-query type affinity, hedged re-dispatch)
whose parameters feed ``_simulate_scan_policy``, and a *stacked* policy
folds a whole policy batch into the lane axis so B_pool × B_policy
candidates score in one dispatch, warm or cold.  ``policy=None`` runs the
untouched legacy kernels — bit-identical to the pre-redesign paths on
every lane (tests/test_routing.py).
"""

from __future__ import annotations

import math
import warnings
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .. import tracing
from .instance import (InstanceType, ModelProfile,
                       bucketed_service_time_lut, service_table_for,
                       service_time_lut, service_time_table)
from .routing import RoutingPolicy
from .telemetry import (BUCKET_EDGES, N_BUCKETS, Telemetry, from_arrays,
                        queue_depth)
from .workload import Workload, WorkloadSpec

_INF = 1e30
# Offset ranking idle slots strictly below any busy slot's next-free time.
# Must be (a) far above any simulated timestamp and (b) small enough that
# float32 keeps unit-spaced priorities distinct after the shift (ulp(1e6) =
# 0.0625).  1e6 simulated seconds is ~11 days of traffic — float32 arrival
# times lose ms resolution two orders of magnitude earlier, so the envelope
# is bounded by the simulator's own precision, not this constant.
_BIG = 1e6
# Guarded horizon of one scan: beyond this, float32 timestamps are so coarse
# (ulp(1e5) ≈ 0.008s) that dispatch ordering and QoS comparisons degrade
# toward the _BIG priority envelope.  Continuous-clock callers must rebase
# (PoolState keeps segment-local times small); exceeding it raises instead
# of silently dispatching to the wrong slot.
_MAX_HORIZON = _BIG / 8.0
# Rank-band separator of the policy dispatch key: an idle slot scores
# ``(type_pref[type(s)] + affinity·svc[s]) · _TIE + priority[s]``, so any
# rank gap >= 1/_TIE dominates the slot-priority tiebreak while exact rank
# ties fall back to pool type order.  2^16 is a power of two, so for the
# identity policy the key is *exactly* ``priority`` in float32
# (0·65536 + p == p), which is what keeps ``policy=None`` and
# ``RoutingPolicy.fcfs`` bit-identical; it also dwarfs ``max_instances``
# (priorities < 64) by three orders of magnitude, so integer-valued
# preference ranks can never be crossed by the tiebreak.
_TIE = 65536.0


def _check_horizon(t_max: float, context: str) -> None:
    if t_max > _MAX_HORIZON:
        raise ValueError(
            f"{context}: simulation horizon {t_max:.4g}s exceeds the safe "
            f"dispatch-priority envelope ({_MAX_HORIZON:.4g}s = _BIG/8); "
            "float32 timestamps this large corrupt the fused idle-vs-busy "
            "dispatch key.  Rebase the episode clock so segment-local times "
            "stay small (PoolState.rebased), or split the stream.")


@dataclass(frozen=True)
class PoolState:
    """Continuous-time carry of an FCFS pool between simulation segments.

    ``free`` holds one next-free time per instance slot in **episode time**
    (float64, monotone across the whole episode); ``clock`` is the episode
    time of the currently bound stream's local ``t=0``, so a scan over
    local arrivals starts from ``free - clock``.  Slots beyond the active
    pool carry placeholder times that no entry point reads.
    """

    free: np.ndarray            # (max_instances,) float64 episode next-free
    clock: float = 0.0          # episode time of the local stream origin

    @classmethod
    def idle(cls, max_instances: int, clock: float = 0.0) -> "PoolState":
        """Fully drained pool: every slot free at ``clock``."""
        return cls(free=np.full(max_instances, float(clock),
                                dtype=np.float64),
                   clock=float(clock))

    def rebased(self, delta: float) -> "PoolState":
        """Shift the local-time origin ``delta`` episode seconds forward.

        Two callers: a phase boundary (``delta`` = the previous stream's
        span, so the next stream's ``t=0`` lands at the previous end) and a
        mid-phase stream rebuild such as a load spike (``delta`` = old minus
        new anchor arrival, keeping the anchor query's episode time
        continuous across the recompression).  Episode-time facts
        (``free``) are untouched — only the mapping moves.
        """
        return PoolState(free=self.free, clock=self.clock + float(delta))

    def remap(self, old_config, new_config, now: float,
              warmup=None) -> "PoolState":
        """Thread slot state through a pool reconfiguration at episode time
        ``now``: per type, the first ``min(old, new)`` slots survive with
        their in-flight work, removed slots drop theirs, and added slots
        start idle at ``now`` (any provisioning delay is the control
        plane's to model *before* the switch takes effect).

        ``warmup`` (per-type seconds, e.g. ``TierCatalog.cold_starts``)
        models capacity-tier cold starts: an *added* slot of type ``t``
        starts busy until ``now + warmup[t]`` instead of idle at ``now`` —
        a pool scaled to zero and re-woken pays its cold-start backlog
        through the same carry as any other queue debt.  Surviving slots
        are already warm and keep their in-flight work untouched."""
        old = np.asarray(old_config, dtype=np.int64)
        new = np.asarray(new_config, dtype=np.int64)
        if old.shape != new.shape or old.ndim != 1:
            raise ValueError("old/new configs must be 1-D with equal length")
        if old.sum() > len(self.free) or new.sum() > len(self.free):
            raise ValueError("config exceeds the state's slot padding")
        free = np.full_like(self.free, float(now))
        oc = np.concatenate([[0], np.cumsum(old)])
        nc = np.concatenate([[0], np.cumsum(new)])
        if warmup is not None:
            w = np.asarray(warmup, dtype=np.float64)
            if w.shape != new.shape:
                raise ValueError("warmup must give one per-type cold-start "
                                 "time matching the config length")
            for t in range(len(new)):
                free[nc[t]:nc[t + 1]] = float(now) + w[t]
        for t in range(len(old)):
            k = int(min(old[t], new[t]))
            free[nc[t]:nc[t] + k] = self.free[oc[t]:oc[t] + k]
        return PoolState(free=free, clock=self.clock)

    def remap_batch(self, old_config, new_configs, now: float,
                    warmup=None) -> np.ndarray:
        """Vectorized what-if remap: the initial carry of every candidate in
        a batch, produced from one live pool's state in one shot.

        Row ``b`` of the returned ``(B, n_slots)`` float64 matrix equals
        ``remap(old_config, new_configs[b], now, warmup).free`` exactly —
        per type, the first ``min(old, new_b)`` slots survive with their
        in-flight work, removed slots drop it, and added slots start idle at
        ``now`` (or busy until ``now + warmup[type]`` under tier cold
        starts).  This is the batched/grid warm lanes' entry ramp: B
        candidate pools scored from the current backlog share one remap and
        one dispatch.
        """
        old = np.asarray(old_config, dtype=np.int64)
        new = np.asarray(new_configs, dtype=np.int64)
        if old.ndim != 1 or new.ndim != 2 or new.shape[1] != len(old):
            raise ValueError("new_configs must be (B, n_types) with n_types "
                             "matching old_config")
        n_slots = len(self.free)
        if old.sum() > n_slots or (new.sum(axis=1) > n_slots).any():
            raise ValueError("config exceeds the state's slot padding")
        n_b = len(new)
        slots = np.arange(n_slots)
        cum = np.cumsum(new, axis=1)                         # (B, T)
        active = slots[None, :] < cum[:, -1:]                # (B, S)
        # Type of each new slot (clamped for inactive slots), its index
        # within the type, and the matching old slot — all closed-form.
        t_of = np.minimum((slots[None, None, :] >= cum[:, :, None]).sum(
            axis=1), len(old) - 1)                           # (B, S)
        rows = np.arange(n_b)[:, None]
        j = slots[None, :] - (cum - new)[rows, t_of]         # idx within type
        survive = active & (j < np.minimum(old, new)[rows, t_of])
        oc = np.concatenate([[0], np.cumsum(old)])
        src = np.clip(oc[:-1][t_of] + j, 0, n_slots - 1)
        base = np.full((n_b, n_slots), float(now))
        if warmup is not None:
            w = np.asarray(warmup, dtype=np.float64)
            if w.shape != old.shape:
                raise ValueError("warmup must give one per-type cold-start "
                                 "time matching the config length")
            # Same float64 sum as the per-row remap: now + warmup[type] for
            # active (added) slots, plain now for the inactive padding.
            base = np.where(active, float(now) + w[t_of], float(now))
        return np.where(survive, self.free[src], base)


@dataclass
class SegmentResult:
    """One warm-start segment: per-query outputs + the carry at any prefix.

    ``lat``/``waits`` cover the whole bound stream.  ``state_at(k)`` is the
    pool state after serving only the first ``k`` queries — the scenario
    engine serves segments speculatively and commits just the prefix it
    consumed before an adaptation cut.  ``state`` (= ``state_at(n)``) is the
    scan's own final carry, bit-exact; interior prefixes are reconstructed
    from the recorded per-query (slot, finish) trace with the same float32
    arithmetic the device performed.  ``telemetry`` is populated by
    ``segment_from(..., telemetry=True)``; window slices come from
    ``PoolSimulator.segment_telemetry``.
    """

    lat: np.ndarray
    waits: np.ndarray
    _state0: "PoolState"
    _active: np.ndarray | None          # (S,) bool; None for empty segments
    _rel0: np.ndarray | None            # (S,) float64 of the f32 carry in
    _fin: np.ndarray | None             # (nq,) float64-exact f32 finishes
    _slots: np.ndarray | None           # (nq,) int dispatch trace
    _final_rel: np.ndarray | None       # (S,) float64 of the f32 carry out
    _start: np.ndarray | None = None    # (nq,) float32 start times
    telemetry: "Telemetry | None" = None

    @property
    def n_queries(self) -> int:
        return len(self.lat)

    @property
    def state(self) -> "PoolState":
        """Carry after the whole segment."""
        return self.state_at(self.n_queries)

    def state_at(self, upto: int) -> "PoolState":
        """Carry after the first ``upto`` served queries."""
        if not 0 <= upto <= self.n_queries:
            raise ValueError(f"upto={upto} outside [0, {self.n_queries}]")
        if self._active is None:        # empty pool or empty stream
            return self._state0
        if upto == self.n_queries:
            rel = self._final_rel
        else:
            # Per-slot finishes are nondecreasing, so max == the last
            # assignment — exactly the scan's carry at step ``upto``.
            rel = self._rel0.copy()
            np.maximum.at(rel, self._slots[:upto], self._fin[:upto])
        free = np.where(self._active, rel + self._state0.clock,
                        self._state0.free)
        return PoolState(free=free, clock=self._state0.clock)


@partial(jax.jit, static_argnames=())
def _simulate_scan(arrivals, service, type_of_slot, priority, free0):
    """FCFS simulation scan from an arbitrary initial carry.

    arrivals:     (nq,)              arrival times (sorted)
    service:      (n_types, nq)      service time of query j on type i
    type_of_slot: (max_inst,) int32  type index of each instance slot
    priority:     (max_inst,)        dispatch order (lower = picked first)
    free0:        (max_inst,)        initial next-free time per slot in the
                                     arrival frame (_INF = slot absent)
    Returns (final next-free carry, (latencies, start_times, slot_idx)).
    """

    def step(free, inputs):
        arrival, svc_by_type = inputs
        # Single fused dispatch key: idle slots rank by type-order priority
        # shifted below any possible next-free time, busy slots by next-free
        # time.  Absent slots carry free == _INF forever, so ``free <=
        # arrival`` is already False and they rank last without an explicit
        # active mask; one argmin picks the identical slot the three-way
        # idle/busy/absent select would: first idle in type order if any,
        # else earliest-freeing.
        key = jnp.where(free <= arrival, priority - _BIG, free)
        slot = jnp.argmin(key)
        start = jnp.maximum(arrival, free[slot])
        finish = start + svc_by_type[type_of_slot[slot]]
        free = free.at[slot].set(finish)
        return free, (finish - arrival, start, slot)

    return jax.lax.scan(step, free0, (arrivals, service.T))


# Batch axis over slot layouts only; the query stream and service table are
# shared.  One executable per (B, nq, max_instances) shape.  The per-slot
# initial carry (free0) maps with the slot layout.
_simulate_scan_batch = jax.jit(
    jax.vmap(_simulate_scan, in_axes=(None, None, 0, None, 0)))

# Grid axes: workloads (stacked arrival streams) × slot layouts.  The service
# table stays shared — load scaling compresses arrivals but keeps batches.
_simulate_scan_grid = jax.jit(
    jax.vmap(jax.vmap(_simulate_scan, in_axes=(None, None, 0, None, 0)),
             in_axes=(0, None, None, None, None)))

# Per-workload service-table flavor: each workload row carries its own
# (n_types, nq) table.  This is the batch-distribution axis (paper Fig. 11,
# scenario dist-drift phases): rows share the arrival stream shape but their
# batch streams — hence service times — differ.
_simulate_scan_grid_tables = jax.jit(
    jax.vmap(jax.vmap(_simulate_scan, in_axes=(None, None, 0, None, 0)),
             in_axes=(0, 0, None, None, None)))

# Unroll factor of the fused QoS-count scan: amortizes while-loop trip
# overhead without changing any per-step arithmetic (bit-identical results).
_GRID_UNROLL = 2


def _qos_threshold_f32(qos_latency: float) -> float:
    """Largest float32 ``t`` with {f32 x: x <= t} == {f32 x: x <= qos}.

    The host paths compare float64-cast latencies against the float64 target;
    the fused grid path compares on-device in float32.  Rounding the target
    *down* to the nearest not-greater float32 makes the two comparisons admit
    exactly the same set of float32 latencies, so the grid's device-side
    counts reproduce the host-side mean bit-for-bit.
    """
    t = np.float32(qos_latency)
    if float(t) > qos_latency:
        t = np.nextafter(t, np.float32(-np.inf))
    return float(t)


_EDGES_DEV = None


def _edges_dev():
    """Device-resident copy of ``BUCKET_EDGES`` (uploaded once per process)."""
    global _EDGES_DEV
    if _EDGES_DEV is None:
        _EDGES_DEV = jnp.asarray(BUCKET_EDGES)
    return _EDGES_DEV


def _grid_lane_qos_counts(arrivals, service_T, type_of_slot, priority, free0,
                          iota, qos_t):
    """QoS-pass count of one (workload, config) lane — the lean FCFS scan.

    Same dispatch recurrence as ``_simulate_scan`` (both take the per-slot
    next-free carry ``free0`` and return the final carry) with two
    fused-engine reductions, neither of which changes a single emitted
    float:
      * the slot update is a one-hot ``where`` instead of a scatter (XLA CPU
        scatters dominate the step cost at these shapes);
      * the QoS comparison accumulates an int32 count in the carry instead of
        materializing (n_queries,) latencies for a host-side mean.
    """

    def step(carry, inputs):
        free, count = carry
        arrival, svc_by_type = inputs
        key = jnp.where(free <= arrival, priority - _BIG, free)
        slot = jnp.argmin(key)
        start = jnp.maximum(arrival, free[slot])
        finish = start + svc_by_type[type_of_slot[slot]]
        free = jnp.where(iota == slot, finish, free)
        count = count + ((finish - arrival) <= qos_t).astype(jnp.int32)
        return (free, count), None

    (free, count), _ = jax.lax.scan(step, (free0, jnp.int32(0)),
                                    (arrivals, service_T),
                                    unroll=_GRID_UNROLL)
    return count, free


# Nested (workload, config) axes: the outer vmap maps arrival streams, the
# inner maps slot layouts (and their initial carries), so a dispatch uploads
# only (W, nq) arrivals plus one (B, S) layout — never a flattened W·B
# replica of either.
_grid_counts_wb = jax.vmap(
    jax.vmap(_grid_lane_qos_counts,
             in_axes=(None, None, 0, None, 0, None, None)),
    in_axes=(0, None, None, None, None, None, None))
_grid_counts_jit = jax.jit(_grid_counts_wb)
# Per-workload service tables (see _simulate_scan_grid_tables): the (nq, T)
# transposed table is mapped with the arrival rows.
_grid_counts_wb_tables = jax.vmap(
    jax.vmap(_grid_lane_qos_counts,
             in_axes=(None, None, 0, None, 0, None, None)),
    in_axes=(0, 0, None, None, None, None, None))
_grid_counts_tables_jit = jax.jit(_grid_counts_wb_tables)
# Per-workload-row initial carries (the ``states=`` grid): free0 gains the
# workload axis — row ``w`` starts every candidate lane from the carry the
# episode entered phase ``w`` with — so a whole multi-phase sweep runs warm
# in one dispatch.
_grid_counts_states_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts,
             in_axes=(None, None, 0, None, 0, None, None)),
    in_axes=(0, None, None, None, 0, None, None)))
_grid_counts_tables_states_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts,
             in_axes=(None, None, 0, None, 0, None, None)),
    in_axes=(0, 0, None, None, 0, None, None)))


def _stream_chunk(free, count, shift, arrivals, batches, valid, lut_T,
                  type_of_slot, priority, iota, qos_t):
    """One streamed query block through the lean FCFS count scan.

    Same dispatch recurrence as ``_grid_lane_qos_counts``, with three
    streaming deltas — none of which changes the arithmetic of a full
    block:

      * service times come from a (max_batch + 1, n_types) lookup-table
        gather over the block's on-device batch sizes (``lut_T[batch]`` is
        bit-equal to the host-built service-table column for that batch,
        see ``instance.service_time_lut``);
      * ``shift`` rebases the carry into a new local time origin before
        the block runs — 0.0 between ordinary blocks, which is a bitwise
        identity (``x - 0.0 == x``, and ``ulp(_INF)`` dwarfs any shift);
      * ``valid`` masks the tail of the final partial block: masked
        queries touch neither the carry nor the count, and an all-True
        block is bit-identical to the unmasked scan.

    ``free``/``count`` are donated (``_stream_chunk_jit``), so a streaming
    consumer holds two small carry buffers plus one block of generated
    queries regardless of episode length.
    """
    free = free - shift

    def step(carry, inputs):
        free, count = carry
        arrival, batch, ok = inputs
        svc_by_type = lut_T[batch]
        key = jnp.where(free <= arrival, priority - _BIG, free)
        slot = jnp.argmin(key)
        start = jnp.maximum(arrival, free[slot])
        finish = start + svc_by_type[type_of_slot[slot]]
        free = jnp.where(ok & (iota == slot), finish, free)
        count = count + (ok & ((finish - arrival) <= qos_t)).astype(
            jnp.int32)
        return (free, count), None

    (free, count), _ = jax.lax.scan(step, (free, count),
                                    (arrivals, batches, valid),
                                    unroll=_GRID_UNROLL)
    return free, count


_stream_chunk_jit = jax.jit(_stream_chunk, donate_argnums=(0, 1))


def _grid_lane_qos_counts_tel(arrivals, service_T, type_of_slot, priority,
                              free0, iota, qos_t, n_active, iota_t, iota_k,
                              edges):
    """Telemetry flavor of ``_grid_lane_qos_counts``: the same dispatch
    recurrence and QoS count, with the full telemetry plane accumulated
    *inside the scan carry* at constant memory — per-type served / QoS-miss
    / busy-millisecond counters, log-bucket latency+wait histograms, and
    integrated/peak queue depth — so a (W, B) sweep never materializes a
    per-query array.  Every accumulator is an int32 add (or max), and every
    float expression (latency, wait, bucket comparison, busy rounding) is
    the identical float32 arithmetic the materializing lanes' finalize pass
    performs, which is what keeps grid-cell telemetry bit-equal to the
    single lane's.  The emitted QoS count is bit-identical to the legacy
    count scan.

    Extra operands: ``n_active`` () int32 active-slot count of this lane,
    ``iota_t`` (n_types,) / ``iota_k`` (N_BUCKETS,) int32 one-hot index
    vectors, ``edges`` (N_BUCKETS - 1,) float32 histogram edges.
    """

    def step(carry, inputs):
        free, count, served, miss, busy, lath, waith, dsum, dpeak = carry
        arrival, svc_by_type = inputs
        idle = free <= arrival
        key = jnp.where(idle, priority - _BIG, free)
        slot = jnp.argmin(key)
        start = jnp.maximum(arrival, free[slot])
        svc = svc_by_type[type_of_slot[slot]]
        finish = start + svc
        free = jnp.where(iota == slot, finish, free)
        lat = finish - arrival
        count = count + (lat <= qos_t).astype(jnp.int32)
        one_t = (iota_t == type_of_slot[slot]).astype(jnp.int32)
        served = served + one_t
        miss = miss + one_t * (lat > qos_t).astype(jnp.int32)
        busy = busy + one_t * jnp.round(svc * 1000.0).astype(jnp.int32)
        wait = jnp.maximum(start - arrival, 0.0)
        lath = lath + (iota_k == (lat >= edges).sum()).astype(jnp.int32)
        waith = waith + (iota_k == (wait >= edges).sum()).astype(jnp.int32)
        depth = n_active - idle.sum().astype(jnp.int32)
        dsum = dsum + depth
        dpeak = jnp.maximum(dpeak, depth)
        return (free, count, served, miss, busy, lath, waith, dsum,
                dpeak), None

    n_t = iota_t.shape[0]
    n_k = iota_k.shape[0]
    zero_t = jnp.zeros(n_t, jnp.int32)
    carry0 = (free0, jnp.int32(0), zero_t, zero_t, zero_t,
              jnp.zeros(n_k, jnp.int32), jnp.zeros(n_k, jnp.int32),
              jnp.int32(0), jnp.int32(0))
    carry, _ = jax.lax.scan(step, carry0, (arrivals, service_T),
                            unroll=_GRID_UNROLL)
    return carry[1:]


# Telemetry grid sweeps run the single-device executable only (the
# shard_map fast path stays telemetry-off: observability sweeps are
# scenario/bench axes, not the BO rescale hot loop).
_TEL_LANE_AXES = (None, None, 0, None, 0, None, None, 0, None, None, None)
_grid_counts_tel_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_tel, in_axes=_TEL_LANE_AXES),
    in_axes=(0,) + (None,) * 10))
_grid_counts_tel_tables_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_tel, in_axes=_TEL_LANE_AXES),
    in_axes=(0, 0) + (None,) * 9))


@jax.jit
def _simulate_scan_policy(arrivals, service, type_of_slot, priority, free0,
                          pref_slot, affinity, hedge):
    """Routed FCFS simulation scan: dispatch driven by policy parameters.

    Same contract as ``_simulate_scan`` plus the per-lane policy operands
    (see ``routing.RoutingPolicy``):

    pref_slot: (max_inst,)  idle preference rank of each slot's *type*
               (``type_pref[type_of_slot]``, folded host-side)
    affinity:  ()           weight of the query's own per-type service time
    hedge:     ()           busy-slot predicted-completion fraction in [0, 1]

    Per query: among slots idle at the arrival instant, minimize
    ``(pref_slot + affinity·svc) · _TIE + priority``; if none is idle,
    minimize ``free + hedge·svc`` (hedge 0 = earliest-freeing FCFS, 1 =
    predicted earliest completion).  Identity parameters (all zeros) pick
    the same slot as the legacy fused key at every step for nonnegative
    arrivals: the idle key collapses to exactly ``priority`` and the busy
    key to exactly ``free`` (tests/test_routing.py asserts the bits).
    Absent slots carry ``free == _INF`` so they are never idle and rank
    last among busy slots, exactly as in the legacy scan.
    """

    def step(free, inputs):
        arrival, svc_by_type = inputs
        svc_slot = svc_by_type[type_of_slot]
        idle = free <= arrival
        idle_key = jnp.where(
            idle, (pref_slot + affinity * svc_slot) * _TIE + priority, _INF)
        busy_key = jnp.where(idle, _INF, free + hedge * svc_slot)
        slot = jnp.where(idle.any(), jnp.argmin(idle_key),
                         jnp.argmin(busy_key))
        start = jnp.maximum(arrival, free[slot])
        finish = start + svc_by_type[type_of_slot[slot]]
        free = free.at[slot].set(finish)
        return free, (finish - arrival, start, slot)

    return jax.lax.scan(step, free0, (arrivals, service.T))


# Policy lane axis: slot layout, initial carry, and the three policy
# operands all map together — a *stacked* policy is folded into this axis
# host-side (``_fold_policy``), so B_pool × B_policy candidates are just
# P·B lanes of one dispatch.  The stream and service table stay shared.
_scan_policy_batch = jax.jit(
    jax.vmap(_simulate_scan_policy,
             in_axes=(None, None, 0, None, 0, 0, 0, 0)))

_scan_policy_grid = jax.jit(
    jax.vmap(jax.vmap(_simulate_scan_policy,
                      in_axes=(None, None, 0, None, 0, 0, 0, 0)),
             in_axes=(0, None, None, None, None, None, None, None)))

_scan_policy_grid_tables = jax.jit(
    jax.vmap(jax.vmap(_simulate_scan_policy,
                      in_axes=(None, None, 0, None, 0, 0, 0, 0)),
             in_axes=(0, 0, None, None, None, None, None, None)))


def _grid_lane_qos_counts_policy(arrivals, service_T, type_of_slot, priority,
                                 free0, iota, qos_t, pref_slot, affinity,
                                 hedge):
    """Routed twin of ``_grid_lane_qos_counts``: the policy dispatch key of
    ``_simulate_scan_policy`` with the lean grid engine's reductions (one-hot
    slot update, QoS count folded into the carry).  Identity parameters
    reproduce the legacy count scan bit for bit."""

    def step(carry, inputs):
        free, count = carry
        arrival, svc_by_type = inputs
        svc_slot = svc_by_type[type_of_slot]
        idle = free <= arrival
        idle_key = jnp.where(
            idle, (pref_slot + affinity * svc_slot) * _TIE + priority, _INF)
        busy_key = jnp.where(idle, _INF, free + hedge * svc_slot)
        slot = jnp.where(idle.any(), jnp.argmin(idle_key),
                         jnp.argmin(busy_key))
        start = jnp.maximum(arrival, free[slot])
        finish = start + svc_by_type[type_of_slot[slot]]
        free = jnp.where(iota == slot, finish, free)
        count = count + ((finish - arrival) <= qos_t).astype(jnp.int32)
        return (free, count), None

    (free, count), _ = jax.lax.scan(step, (free0, jnp.int32(0)),
                                    (arrivals, service_T),
                                    unroll=_GRID_UNROLL)
    return count, free


# Nested (workload, policy·config-lane) axes.  The folded P·B lane axis is
# an ordinary batch axis, so the routed grid shards across XLA host devices
# exactly like the plain one (``_dispatch_grid_sharded`` splits whichever of
# the workload / lane axes costs less, mapping the policy operands with the
# lanes).
_grid_counts_policy_wb = jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy,
             in_axes=(None, None, 0, None, 0, None, None, 0, 0, 0)),
    in_axes=(0, None, None, None, None, None, None, None, None, None))
_grid_counts_policy_jit = jax.jit(_grid_counts_policy_wb)
_grid_counts_policy_wb_tables = jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy,
             in_axes=(None, None, 0, None, 0, None, None, 0, 0, 0)),
    in_axes=(0, 0, None, None, None, None, None, None, None, None))
_grid_counts_policy_tables_jit = jax.jit(_grid_counts_policy_wb_tables)
# Routed ``states=`` grid: per-workload-row initial carries (see the plain
# states jits above).
_grid_counts_policy_states_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy,
             in_axes=(None, None, 0, None, 0, None, None, 0, 0, 0)),
    in_axes=(0, None, None, None, 0, None, None, None, None, None)))
_grid_counts_policy_tables_states_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy,
             in_axes=(None, None, 0, None, 0, None, None, 0, 0, 0)),
    in_axes=(0, 0, None, None, 0, None, None, None, None, None)))


# ---------------------------------------------------------------------------
# shard_map lane sharding (replaces the single-process pmap opt-in): the
# flattened grid is laid out over a 1-D "lane" mesh of the configured XLA
# host devices (or real chips on accelerator backends).  Under jit the
# shard_mapped executable takes *global* operands — callers cyclic-pad the
# split axis to a device multiple and slice the result, no (n_dev, ...)
# leading-axis reshape — and per-device blocks run the identical per-lane
# vmap bodies, so sharded counts match the single-device jits bit for bit.
# ---------------------------------------------------------------------------
_MESHES: dict[int, Mesh] = {}


def _lane_mesh(n_dev: int) -> Mesh:
    mesh = _MESHES.get(n_dev)
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("lane",))
        _MESHES[n_dev] = mesh
    return mesh


# flavor -> (per-device vmap body, workload-split arg indices,
#            lane-split arg indices).  Workload-split shards arrival rows
# (and, for the tables flavors, the matching service-table rows);
# lane-split shards slot layouts + carries (+ the per-lane policy operands).
_SHARD_FLAVORS = {
    "plain": (_grid_counts_wb, (0,), (2, 4)),
    "tables": (_grid_counts_wb_tables, (0, 1), (2, 4)),
    "policy": (_grid_counts_policy_wb, (0,), (2, 4, 7, 8, 9)),
    "policy_tables": (_grid_counts_policy_wb_tables, (0, 1), (2, 4, 7, 8, 9)),
}
_N_SHARD_ARGS = {"plain": 7, "tables": 7, "policy": 10, "policy_tables": 10}
_SHARDED_FNS: dict[tuple, object] = {}


def _sharded_counts_fn(n_dev: int, flavor: str, axis: str):
    """Compiled shard_mapped grid-counts executable, cached per
    (device count, kernel flavor, split axis)."""
    cache_key = (n_dev, flavor, axis)
    fn = _SHARDED_FNS.get(cache_key)
    if fn is None:
        base, w_args, l_args = _SHARD_FLAVORS[flavor]
        n_args = _N_SHARD_ARGS[flavor]
        split = w_args if axis == "w" else l_args
        in_specs = tuple(P("lane") if i in split else P()
                         for i in range(n_args))
        # Splitting workloads shards the (W, B) result rows; splitting
        # lanes shards its columns.
        out_specs = ((P("lane"), P("lane")) if axis == "w"
                     else (P(None, "lane"), P(None, "lane")))
        fn = jax.jit(jax.shard_map(base, mesh=_lane_mesh(n_dev),
                                   in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        _SHARDED_FNS[cache_key] = fn
    return fn


def _grid_lane_qos_counts_policy_tel(arrivals, service_T, type_of_slot,
                                     priority, free0, iota, qos_t, n_active,
                                     iota_t, iota_k, edges, pref_slot,
                                     affinity, hedge):
    """Routed twin of ``_grid_lane_qos_counts_tel``: the policy dispatch key
    of ``_simulate_scan_policy`` with the in-carry telemetry accumulators.
    Identity parameters reproduce the legacy telemetry count scan bit for
    bit (the idle test and every accumulator expression are shared)."""

    def step(carry, inputs):
        free, count, served, miss, busy, lath, waith, dsum, dpeak = carry
        arrival, svc_by_type = inputs
        svc_slot = svc_by_type[type_of_slot]
        idle = free <= arrival
        idle_key = jnp.where(
            idle, (pref_slot + affinity * svc_slot) * _TIE + priority, _INF)
        busy_key = jnp.where(idle, _INF, free + hedge * svc_slot)
        slot = jnp.where(idle.any(), jnp.argmin(idle_key),
                         jnp.argmin(busy_key))
        start = jnp.maximum(arrival, free[slot])
        svc = svc_by_type[type_of_slot[slot]]
        finish = start + svc
        free = jnp.where(iota == slot, finish, free)
        lat = finish - arrival
        count = count + (lat <= qos_t).astype(jnp.int32)
        one_t = (iota_t == type_of_slot[slot]).astype(jnp.int32)
        served = served + one_t
        miss = miss + one_t * (lat > qos_t).astype(jnp.int32)
        busy = busy + one_t * jnp.round(svc * 1000.0).astype(jnp.int32)
        wait = jnp.maximum(start - arrival, 0.0)
        lath = lath + (iota_k == (lat >= edges).sum()).astype(jnp.int32)
        waith = waith + (iota_k == (wait >= edges).sum()).astype(jnp.int32)
        depth = n_active - idle.sum().astype(jnp.int32)
        dsum = dsum + depth
        dpeak = jnp.maximum(dpeak, depth)
        return (free, count, served, miss, busy, lath, waith, dsum,
                dpeak), None

    n_t = iota_t.shape[0]
    n_k = iota_k.shape[0]
    zero_t = jnp.zeros(n_t, jnp.int32)
    carry0 = (free0, jnp.int32(0), zero_t, zero_t, zero_t,
              jnp.zeros(n_k, jnp.int32), jnp.zeros(n_k, jnp.int32),
              jnp.int32(0), jnp.int32(0))
    carry, _ = jax.lax.scan(step, carry0, (arrivals, service_T),
                            unroll=_GRID_UNROLL)
    return carry[1:]


_TEL_POLICY_AXES = _TEL_LANE_AXES + (0, 0, 0)
_grid_counts_policy_tel_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy_tel, in_axes=_TEL_POLICY_AXES),
    in_axes=(0,) + (None,) * 13))
_grid_counts_policy_tel_tables_jit = jax.jit(jax.vmap(
    jax.vmap(_grid_lane_qos_counts_policy_tel, in_axes=_TEL_POLICY_AXES),
    in_axes=(0, 0) + (None,) * 12))


def _fold_policy(policy: RoutingPolicy, type_of_slot: np.ndarray,
                 free0: np.ndarray) -> tuple:
    """Fold a policy's (optional) stacked axis into the lane axis.

    ``type_of_slot`` (B, S) int32 and ``free0`` (B, S) are the batch lane
    operands; the per-type preference table is gathered to per-*slot* rows
    here so the kernel never indexes by type at dispatch time.  Returns
    ``(type_of_slot, free0, pref_slot, affinity, hedge, n_policies)`` with
    a P·B lane axis for a stacked policy — policy-major, lane ``p·B + b``
    is (policy ``p``, config ``b``) — and the original B lanes otherwise.
    """
    pref = np.asarray(policy.type_pref, dtype=np.float32)
    n_b, n_s = type_of_slot.shape
    if pref.ndim == 1:
        return (type_of_slot, free0, pref[type_of_slot],
                np.full(n_b, policy.affinity, dtype=np.float32),
                np.full(n_b, policy.hedge, dtype=np.float32), 1)
    n_p = len(pref)
    return (np.tile(type_of_slot, (n_p, 1)), np.tile(free0, (n_p, 1)),
            pref[:, type_of_slot].reshape(n_p * n_b, n_s),
            np.repeat(np.asarray(policy.affinity, dtype=np.float32), n_b),
            np.repeat(np.asarray(policy.hedge, dtype=np.float32), n_b), n_p)


def _cold_free0(active: np.ndarray) -> np.ndarray:
    """(..., S) float32 idle initial carry: 0 for active slots, _INF for
    absent ones — bitwise the carry the scan built internally before warm
    starts existed, which is what keeps the cold paths bit-identical."""
    return np.where(active, np.float32(0.0), np.float32(_INF))


def _expand_slots(configs, n_types: int,
                  max_instances: int) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized config→slot expansion for a (B, n_types) batch.

    Slot ``s`` of row ``b`` holds type ``t`` iff
    ``cumsum(configs[b])[t-1] <= s < cumsum(configs[b])[t]``; counting the
    cumulative sums <= s gives ``t`` without any per-slot loop.
    Returns (type_of_slot (B, max_inst) int32, active (B, max_inst) bool).
    Module-level so the streaming simulator (which owns no PoolSimulator)
    shares the identical layout arithmetic.
    """
    counts = np.asarray(configs, dtype=np.int64)
    if counts.ndim != 2 or counts.shape[1] != n_types:
        raise ValueError(f"expected (B, {n_types}) config batch, "
                         f"got shape {counts.shape}")
    cum = np.cumsum(counts, axis=1)                      # (B, T)
    total = cum[:, -1]
    if (total > max_instances).any():
        raise ValueError("config exceeds max_instances padding")
    slots = np.arange(max_instances)
    active = slots[None, :] < total[:, None]             # (B, S)
    type_of_slot = (slots[None, None, :] >= cum[:, :, None]).sum(
        axis=1).astype(np.int32)                         # (B, S)
    return np.where(active, type_of_slot, 0).astype(np.int32), active


# Bit layout of the packed per-query word the telemetry twin scans emit:
# slot index in the low bits, the slot's type above it, the queue depth
# (busy active slots just before dispatch) on top.  Ten bits per field
# bounds pools at 1024 slots/types — far above any catalog in the repo.
_PACK_T = 10
_PACK_D = 20


def _simulate_scan_tel(arrivals, service, type_of_slot, priority, free0,
                       n_active, iota):
    """Telemetry twin of ``_simulate_scan``: the identical dispatch
    arithmetic — latencies, starts, and chosen slots are bit-identical by
    construction — plus the per-step queue depth measured in place from the
    carry (``n_active`` minus the idle count the dispatch key already
    needed) and packed with the slot and its type into one int32 output.
    The twin runs on occupancy-trimmed slot operands with the one-hot
    carry update of the lean grid kernels; both are invisible to the
    results (inactive slots never win the argmin, and ``where(iota ==
    slot)`` writes the very value the positional update would), and
    together they make the telemetry lane cheaper than the legacy scan it
    twins — which is what holds the bench's ≤10 % overhead gate.
    """

    def step(free, inputs):
        arrival, svc_by_type = inputs
        idle = free <= arrival
        key = jnp.where(idle, priority - _BIG, free)
        slot = jnp.argmin(key)
        start = jnp.maximum(arrival, free[slot])
        tslot = type_of_slot[slot]
        finish = start + svc_by_type[tslot]
        free = jnp.where(iota == slot, finish, free)
        depth = n_active - idle.sum().astype(jnp.int32)
        packed = (slot.astype(jnp.int32) | (tslot << _PACK_T)
                  | (depth << _PACK_D))
        return free, (finish - arrival, start, packed)

    return jax.lax.scan(step, free0, (arrivals, service.T))


def _simulate_scan_policy_tel(arrivals, service, type_of_slot, priority,
                              free0, pref_slot, affinity, hedge, n_active,
                              iota):
    """Telemetry twin of ``_simulate_scan_policy`` — same contract and
    bit-identity argument as ``_simulate_scan_tel``."""

    def step(free, inputs):
        arrival, svc_by_type = inputs
        svc_slot = svc_by_type[type_of_slot]
        idle = free <= arrival
        idle_key = jnp.where(
            idle, (pref_slot + affinity * svc_slot) * _TIE + priority, _INF)
        busy_key = jnp.where(idle, _INF, free + hedge * svc_slot)
        slot = jnp.where(idle.any(), jnp.argmin(idle_key),
                         jnp.argmin(busy_key))
        start = jnp.maximum(arrival, free[slot])
        tslot = type_of_slot[slot]
        finish = start + svc_by_type[tslot]
        free = jnp.where(iota == slot, finish, free)
        depth = n_active - idle.sum().astype(jnp.int32)
        packed = (slot.astype(jnp.int32) | (tslot << _PACK_T)
                  | (depth << _PACK_D))
        return free, (finish - arrival, start, packed)

    return jax.lax.scan(step, free0, (arrivals, service.T))


# Lane axes mirror the primary kernels': slot layout, carry, and active
# count map with the lane; the stream, service table, and trimmed iota are
# shared.  Grid variants add the workload axis over arrivals (and over the
# per-workload service tables for the tables flavor).
_TEL_SCAN_AXES = (None, None, 0, None, 0, 0, None)
_scan_tel_batch = jax.jit(jax.vmap(_simulate_scan_tel,
                                   in_axes=_TEL_SCAN_AXES))
_scan_tel_grid = jax.jit(jax.vmap(
    jax.vmap(_simulate_scan_tel, in_axes=_TEL_SCAN_AXES),
    in_axes=(0,) + (None,) * 6))
_scan_tel_grid_tables = jax.jit(jax.vmap(
    jax.vmap(_simulate_scan_tel, in_axes=_TEL_SCAN_AXES),
    in_axes=(0, 0) + (None,) * 5))

_TEL_SCAN_POLICY_AXES = (None, None, 0, None, 0, 0, 0, 0, 0, None)
_scan_policy_tel_batch = jax.jit(jax.vmap(
    _simulate_scan_policy_tel, in_axes=_TEL_SCAN_POLICY_AXES))
_scan_policy_tel_grid = jax.jit(jax.vmap(
    jax.vmap(_simulate_scan_policy_tel, in_axes=_TEL_SCAN_POLICY_AXES),
    in_axes=(0,) + (None,) * 9))
_scan_policy_tel_grid_tables = jax.jit(jax.vmap(
    jax.vmap(_simulate_scan_policy_tel, in_axes=_TEL_SCAN_POLICY_AXES),
    in_axes=(0, 0) + (None,) * 8))


def _tel_finalize(lat, start, packed, arrivals, service, qos_t, edges):
    """Device telemetry reduction over one lane's twin-scan outputs.

    The twin scans emit per-query (latency, start, packed slot/type/depth),
    so telemetry is a data-parallel post-pass over arrays the lane already
    materialized: per-type one-hot sums for the served / QoS-miss /
    busy-millisecond counters, comparison-count bucketing folded into
    adjacent differences for the two histograms (no scatters — XLA CPU
    lowers them to row-at-a-time loops), and a straight sum/max over the
    queue depth the scan measured in place.  Every float expression
    (latency, wait, bucket comparison, busy rounding) is the identical
    float32 arithmetic of the in-carry grid kernel and the host mirror,
    which is what keeps all three telemetry styles bit-equal.

    Returns int32 (served, miss, busy_ms) per type, (lat_hist, wait_hist)
    per bucket, and scalar (depth_sum, depth_peak).
    """
    nq = lat.shape[0]
    n_types = service.shape[0]
    tslot = (packed >> _PACK_T) & ((1 << (_PACK_D - _PACK_T)) - 1)
    depth = packed >> _PACK_D
    onehot = tslot[:, None] == jnp.arange(n_types, dtype=tslot.dtype)[None, :]
    served = onehot.astype(jnp.int32).sum(axis=0)
    miss = (onehot & (lat > qos_t)[:, None]).astype(jnp.int32).sum(axis=0)
    svc = service[tslot, jnp.arange(nq)]
    ms = jnp.round(svc * 1000.0).astype(jnp.int32)
    busy_ms = jnp.where(onehot, ms[:, None], 0).sum(axis=0)
    wait = jnp.maximum(start - arrivals, 0.0)

    def hist(x):
        # #{x in bucket k} from >=-edge counts: identical comparisons to
        # the in-carry kernel's ``(x >= edges).sum()`` bucket index, folded
        # to adjacent differences so no per-query one-hot row ever exists.
        cnt = (x[:, None] >= edges).astype(jnp.int32).sum(axis=0)
        return jnp.concatenate([jnp.int32(nq)[None] - cnt[:1],
                                cnt[:-1] - cnt[1:], cnt[-1:]])

    return (served, miss, busy_ms, hist(lat), hist(wait), depth.sum(),
            depth.max())


# (lat, start, packed, arrivals, service, qos_t, edges): lane-mapped
# outputs, shared stream/table/consts; grid variants map arrivals (and the
# per-workload service table for the tables flavor) with the workload axis.
_TEL_FIN_AXES = (0, 0, 0, None, None, None, None)
_tel_finalize_batch = jax.jit(jax.vmap(_tel_finalize, in_axes=_TEL_FIN_AXES))
_tel_finalize_grid = jax.jit(jax.vmap(
    jax.vmap(_tel_finalize, in_axes=_TEL_FIN_AXES),
    in_axes=(0, 0, 0, 0, None, None, None)))
_tel_finalize_grid_tables = jax.jit(jax.vmap(
    jax.vmap(_tel_finalize, in_axes=_TEL_FIN_AXES),
    in_axes=(0, 0, 0, 0, 0, None, None)))


def _device_telemetry(parts, n_types, zero=None, shape=None) -> Telemetry:
    """Assemble a host :class:`Telemetry` from device accumulator parts
    (int32 → int64), zeroing all-zero-config lanes (their scan outputs are
    garbage the primary paths also overwrite host-side) and optionally
    unfolding a stacked-policy lane axis."""
    served, miss, busy, lath, waith, dsum, dpeak = [
        np.asarray(jax.device_get(p), dtype=np.int64) for p in parts]
    if zero is not None and np.asarray(zero).any():
        for a in (served, miss, busy, lath, waith):
            a[..., zero, :] = 0
        dsum[..., zero] = 0
        dpeak[..., zero] = 0
    if shape is not None:
        served = served.reshape(shape + served.shape[-1:])
        miss = miss.reshape(shape + miss.shape[-1:])
        busy = busy.reshape(shape + busy.shape[-1:])
        lath = lath.reshape(shape + lath.shape[-1:])
        waith = waith.reshape(shape + waith.shape[-1:])
        dsum = dsum.reshape(shape)
        dpeak = dpeak.reshape(shape)
    return Telemetry(served=served, miss=miss, busy_ms=busy, lat_hist=lath,
                     wait_hist=waith, depth_sum=dsum, depth_peak=dpeak)


@dataclass
class SimResult:
    """Per-query outcome of one ``PoolSimulator.simulate`` call.

    ``lat`` carries end-to-end latencies shaped by the lane the call took:
    (n_queries,) single, (B, n_queries) batch, (P, B, n_queries) stacked
    policy × batch, (W, [P,] B, n_queries) workload grid.  ``waits`` (queue
    time, ``start − arrival`` clamped at zero) is populated on the single
    lane only — batch/grid lanes keep the lean device path.  ``state`` is
    the final continuous-clock carry for warm-start calls: a
    :class:`PoolState` (single), a list of them (batch), or a [P][B] nested
    list (stacked policy × batch); ``None`` on cold and grid lanes.
    ``telemetry`` (``telemetry=True`` calls only) is a
    :class:`~repro.serving.telemetry.Telemetry` whose leading dims mirror
    the lane.
    """

    lat: np.ndarray
    waits: np.ndarray | None
    state: object | None
    telemetry: "Telemetry | None" = None


@dataclass
class QosResult:
    """QoS outcome of one ``PoolSimulator.qos`` call.

    ``rates`` is the fraction of queries within the model's QoS latency —
    a float (single lane), (B,) or (P, B) (batch lanes), or (W, [P,] B)
    (workload grid).  ``state`` mirrors :class:`SimResult.state`;
    ``telemetry`` mirrors :class:`SimResult.telemetry` (grid calls ride
    the in-carry accumulators, so only the counters cross to the host).
    """

    rates: float | np.ndarray
    state: object | None
    telemetry: "Telemetry | None" = None


# Legacy names that already warned this process — shim warnings fire once
# per name, not per call (tests clear this set to re-arm them).
_WARNED: set[str] = set()


def _warn_deprecated(name: str, alt: str) -> None:
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(
        f"PoolSimulator.{name}() is deprecated; use PoolSimulator.{alt} "
        f"(migration table: docs/api_migration.md)",
        DeprecationWarning, stacklevel=3)


def _fetch(x, lane: str, request: int | None, **args):
    """A lane's device result on the host: the host blocks on the device
    (span ``sim.wait``, with ``args`` besides the lane)."""
    with tracing.span("sim.wait", request, lane=lane, **args):
        return jax.device_get(x)


@dataclass(frozen=True)
class _GridPending:
    """A grid QoS dispatch issued and not yet fetched
    (``PoolSimulator._qos_grid_issue``): the device's (W, L) counts, the
    real block of them (``[:n_w, :n_l]``: the sharded path pads load
    levels or lanes) and the shape of the rates.  Where the rates needed
    no wait here (an empty grid, or the telemetry twin, which fetches its
    own counts), they are in ``rates`` and ``counts`` is None."""

    counts: object
    n_w: int
    n_l: int
    shape: tuple
    rates: np.ndarray | None = None
    telemetry: "Telemetry | None" = None


class PoolSimulator:
    """Simulator bound to (model profile, instance type order, workload).

    Host work a lane does before its dispatch is traced as ``sim.stage``,
    and the wait for its result as ``sim.wait`` (``repro.tracing``)."""

    def __init__(self, model: ModelProfile, types: list[InstanceType],
                 workload: Workload, max_instances: int = 40):
        self.model = model
        self.types = list(types)
        self.workload = workload
        self.max_instances = max_instances
        if workload.n_queries:
            _check_horizon(float(workload.arrivals[-1]),
                           "PoolSimulator workload")
        # Bucket-aware selector: a stream annotated with request-size
        # buckets binds a per-query table built from bucket-scaled
        # profiles, so bucketed traffic rides every lane below (cold,
        # warm, batch, grid, routed) with no kernel changes.  Scalar
        # streams bind the legacy table bit for bit.
        self._service = jnp.asarray(
            service_table_for(model, self.types, workload),
            dtype=jnp.float32)
        self._service_host: np.ndarray | None = None   # lazy host mirror
        self._arrivals = jnp.asarray(workload.arrivals, dtype=jnp.float32)
        self._priority = jnp.arange(max_instances, dtype=jnp.float32)
        # Grid-engine device caches: replicated constants per (n_dev, width)
        # and arrival grids per load-factor tuple (rescale loops re-sweep the
        # same monitored levels every round).  Both are small and bounded;
        # _grid_arrs is LRU (hits refresh recency, see _grid_arr_shards).
        self._grid_consts: dict[tuple, tuple] = {}
        self._grid_arrs: dict[tuple, jnp.ndarray] = {}
        self.request = tracing.new_request()

    def _slots_batch(self, configs) -> tuple[np.ndarray, np.ndarray]:
        """Config→slot expansion for a (B, n_types) batch (see
        ``_expand_slots``)."""
        return _expand_slots(configs, len(self.types), self.max_instances)

    def _slots(self, config) -> tuple[np.ndarray, np.ndarray]:
        type_of_slot, active = self._slots_batch(
            np.asarray(config, dtype=np.int64)[None, :])
        return type_of_slot[0], active[0]

    # --------------------------------------------------- unified surface
    def _check_policy(self, policy) -> RoutingPolicy | None:
        if policy is None:
            return None
        if not isinstance(policy, RoutingPolicy):
            raise TypeError("policy must be a RoutingPolicy or None, got "
                            f"{type(policy).__name__}")
        return policy.check_pool(len(self.types))

    @staticmethod
    def _check_warm_kwargs(state, deployed, now, warmup) -> None:
        if state is None and not (deployed is None and now is None
                                  and warmup is None):
            raise ValueError("deployed=/now=/warmup= describe a warm-start "
                             "redeploy and require state=")

    def simulate(self, configs, *, state=None, workloads=None,
                 service_tables=None, policy=None, deployed=None, now=None,
                 warmup=None, telemetry: bool = False) -> "SimResult":
        """Serve the bound stream — every lane, one entrypoint.

        The lane is picked by the arguments, not the method name:

        * ``configs`` (n_types,) — **single** pool.  ``lat``/``waits`` are
          (n_queries,); with ``state=`` the segment starts from that
          continuous-clock carry and ``result.state`` is the final carry.
        * ``configs`` (B, n_types) — **batch**: B pools in one dispatch,
          ``lat`` (B, n_queries) (``waits`` stays ``None``).  With
          ``state=`` each candidate runs from the live carry —
          ``deployed=``/``now=``/``warmup=`` remap it per candidate
          exactly as ``PoolState.remap`` would — and ``result.state`` is
          the per-candidate final carries.
        * ``workloads=`` (W load factors) — **grid**: W scaled arrival
          streams × the config batch, ``lat`` (W, B, n_queries);
          ``service_tables=`` (W, n_types, n_queries) gives each workload
          row its own table (the batch-distribution axis).
        * ``policy=`` a :class:`~repro.serving.routing.RoutingPolicy`
          routes dispatch on any lane; a *stacked* policy adds a leading
          policy axis — ``lat`` (P, B, n_queries) / (W, P, B, n_queries) —
          scored in the same single dispatch.  ``policy=None`` runs the
          untouched legacy FCFS kernels, bit-identical to the pre-redesign
          methods on every lane.

        All-zero configs serve nothing (+inf latencies, zero telemetry).
        ``telemetry=True`` additionally returns a
        :class:`~repro.serving.telemetry.Telemetry` per lane — the primary
        outputs are bit-identical either way: telemetry-off keeps the
        untouched legacy kernels, telemetry-on swaps in twin scans with the
        identical dispatch arithmetic that also measure queue depth in
        place, plus a data-parallel device finalize for the counters and
        histograms.  The legacy ``latencies*``/``qos_rate*`` names delegate
        here and warn (docs/api_migration.md maps every old call).
        """
        policy = self._check_policy(policy)
        self._check_warm_kwargs(state, deployed, now, warmup)
        cfg = np.asarray(configs, dtype=np.int64)
        if workloads is not None:
            if cfg.ndim != 2:
                raise ValueError("the workload grid needs a (B, n_types) "
                                 "config batch")
            lat, tel = self._sim_grid(cfg, workloads, service_tables, policy,
                                      state, deployed, now, warmup,
                                      telemetry)
            return SimResult(lat=lat, waits=None, state=None, telemetry=tel)
        if service_tables is not None:
            raise ValueError("service_tables is a workload-grid axis; pass "
                             "workloads= as well")
        if cfg.ndim == 1:
            if policy is not None and policy.stacked:
                raise ValueError(
                    "a stacked policy needs a config batch; pass "
                    "configs=[config] to score one pool under P policies")
            if state is not None:
                seg = self.segment_from(state, cfg, policy=policy,
                                        telemetry=telemetry)
                return SimResult(lat=seg.lat, waits=seg.waits,
                                 state=seg.state, telemetry=seg.telemetry)
            if telemetry:
                # The idle carry at clock 0 is the warm identity element, so
                # the segment lane reproduces the cold bits exactly — and
                # already knows how to attach telemetry.
                seg = self.segment_from(self.initial_state(), cfg,
                                        policy=policy, telemetry=True)
                return SimResult(lat=seg.lat, waits=seg.waits, state=None,
                                 telemetry=seg.telemetry)
            lat, waits = self._lat_waits_single(cfg, policy)
            return SimResult(lat=lat, waits=waits, state=None)
        if cfg.ndim != 2:
            raise ValueError("configs must be (n_types,) or (B, n_types), "
                             f"got shape {cfg.shape}")
        if state is not None:
            lat, states, tel = self._sim_batch_from(state, cfg, policy,
                                                    deployed, now, warmup,
                                                    telemetry)
            return SimResult(lat=lat, waits=None, state=states,
                             telemetry=tel)
        lat, tel = self._sim_batch(cfg, policy, telemetry)
        return SimResult(lat=lat, waits=None, state=None, telemetry=tel)

    def qos(self, configs, *, state=None, states=None, workloads=None,
            service_tables=None, policy=None, deployed=None, now=None,
            warmup=None, telemetry: bool = False) -> "QosResult":
        """QoS satisfaction rates — ``simulate``'s lanes, lean reductions.

        Same argument-driven lane selection as :meth:`simulate` (single /
        batch / grid × cold / warm × ``policy=``), returning the fraction
        of queries within ``model.qos_latency`` (paper Eq. 2 R_sat).  The
        grid lane runs the fused count scan — only (W, [P·]B) int32 counts
        cross back to the host — and the single cold lane skips the waits
        materialization, so sequential baselines stay honest.  Rates agree
        with ``simulate(...)`` + a host-side threshold mean bit for bit.
        ``telemetry=True`` attaches per-lane telemetry; rates stay
        bit-identical (the grid lane swaps to the in-carry telemetry scan,
        whose QoS count is the same arithmetic; other lanes just add the
        device post-pass).

        ``states=`` is the grid lane's *per-workload-row* warm start: one
        entry per workload row, each ``None`` (cold) or a ``(PoolState,
        deployed_config)`` pair — row ``w`` then scores every candidate
        from the carry the episode held entering that phase, so a whole
        multi-phase sweep runs warm in one dispatch.  Mutually exclusive
        with the single shared ``state=`` and with ``telemetry=``.
        """
        if workloads is not None:
            pending = self._qos_grid_issue(
                configs, workloads, service_tables=service_tables,
                policy=policy, state=state, states=states,
                deployed=deployed, now=now, warmup=warmup,
                telemetry=telemetry)
            return QosResult(rates=self._qos_grid_fetch(pending), state=None,
                             telemetry=pending.telemetry)
        policy = self._check_policy(policy)
        if states is not None:
            raise ValueError("states= is a per-workload-row grid axis; "
                             "pass workloads= as well")
        self._check_warm_kwargs(state, deployed, now, warmup)
        cfg = np.asarray(configs, dtype=np.int64)
        if service_tables is not None:
            raise ValueError("service_tables is a workload-grid axis; pass "
                             "workloads= as well")
        if cfg.ndim == 1:
            if policy is not None and policy.stacked:
                raise ValueError(
                    "a stacked policy needs a config batch; pass "
                    "configs=[config] to score one pool under P policies")
            if state is not None:
                seg = self.segment_from(state, cfg, policy=policy,
                                        telemetry=telemetry)
                rate = float(np.mean(seg.lat <= self.model.qos_latency))
                return QosResult(rates=rate, state=seg.state,
                                 telemetry=seg.telemetry)
            if telemetry:
                seg = self.segment_from(self.initial_state(), cfg,
                                        policy=policy, telemetry=True)
                rate = float(np.mean(seg.lat <= self.model.qos_latency))
                return QosResult(rates=rate, state=None,
                                 telemetry=seg.telemetry)
            lat = self._lat_single(cfg, policy)
            return QosResult(
                rates=float(np.mean(lat <= self.model.qos_latency)),
                state=None)
        if cfg.ndim != 2:
            raise ValueError("configs must be (n_types,) or (B, n_types), "
                             f"got shape {cfg.shape}")
        if state is not None:
            lat, states, tel = self._sim_batch_from(state, cfg, policy,
                                                    deployed, now, warmup,
                                                    telemetry)
            return QosResult(rates=np.mean(lat <= self.model.qos_latency,
                                           axis=-1), state=states,
                             telemetry=tel)
        lat, tel = self._sim_batch(cfg, policy, telemetry)
        return QosResult(rates=np.mean(lat <= self.model.qos_latency,
                                       axis=-1), state=None, telemetry=tel)

    def tail_latency(self, config, pct: float = 99.0, *, state=None,
                     policy=None) -> float:
        """Tail latency of one pool config, derived from the telemetry
        plane's log-bucket histogram (the upper edge of the bucket where
        the CDF crosses the rank — within one bucket of the exact sample
        percentile).  Accepts ``state=``/``policy=`` like ``simulate``, so
        warm tails and routed tails ride the same unified surface instead
        of the old cold-only re-simulation."""
        r = self.qos(config, state=state, policy=policy, telemetry=True)
        return r.telemetry.latency_percentile(pct)

    # -------------------------------------------------- single-lane cores
    def _policy_single_args(self, policy: RoutingPolicy,
                            type_of_slot: np.ndarray) -> tuple:
        pref = np.asarray(policy.type_pref, dtype=np.float32)
        return (jnp.asarray(pref[type_of_slot]), jnp.float32(policy.affinity),
                jnp.float32(policy.hedge))

    def _lat_single(self, config, policy) -> np.ndarray:
        """Per-query end-to-end latency (wait + service) for a pool config."""
        if sum(int(c) for c in config) == 0:
            return np.full(self.workload.n_queries, np.inf)
        with tracing.span("sim.stage", self.request, lane="single"):
            type_of_slot, active = self._slots(config)
            free0 = jnp.asarray(_cold_free0(active))
            if policy is None:
                _, (lat, _, _) = _simulate_scan(
                    self._arrivals, self._service, jnp.asarray(type_of_slot),
                    self._priority, free0)
            else:
                pref, aff, hed = self._policy_single_args(policy,
                                                          type_of_slot)
                _, (lat, _, _) = _simulate_scan_policy(
                    self._arrivals, self._service, jnp.asarray(type_of_slot),
                    self._priority, free0, pref, aff, hed)
        return np.asarray(_fetch(lat, "single", self.request),
                          dtype=np.float64)

    def _lat_waits_single(self, config,
                          policy) -> tuple[np.ndarray, np.ndarray]:
        """Per-query (latency, queue wait) arrays for a pool config.

        The wait is ``start - arrival`` — exactly the queue time the paper's
        load monitor watches ("more queries get queued in the query queue").
        The latencies equal ``_lat_single`` bit for bit (same scan, same
        outputs); waits come from the scan's start times clamped at zero
        against the float32 arrival cast.
        """
        n = self.workload.n_queries
        if sum(int(c) for c in config) == 0:
            return np.full(n, np.inf), np.full(n, np.inf)
        type_of_slot, active = self._slots(config)
        free0 = jnp.asarray(_cold_free0(active))
        if policy is None:
            _, (lat, start, _) = _simulate_scan(
                self._arrivals, self._service, jnp.asarray(type_of_slot),
                self._priority, free0)
        else:
            pref, aff, hed = self._policy_single_args(policy, type_of_slot)
            _, (lat, start, _) = _simulate_scan_policy(
                self._arrivals, self._service, jnp.asarray(type_of_slot),
                self._priority, free0, pref, aff, hed)
        lat = np.asarray(jax.device_get(lat), dtype=np.float64)
        start = np.asarray(jax.device_get(start), dtype=np.float64)
        arr = np.asarray(jax.device_get(self._arrivals), dtype=np.float64)
        return lat, np.maximum(start - arr, 0.0)

    def latencies(self, config) -> np.ndarray:
        """Deprecated: ``simulate(config).lat``."""
        _warn_deprecated("latencies", "simulate(config).lat")
        return self.simulate(config).lat

    def latencies_waits(self, config) -> tuple[np.ndarray, np.ndarray]:
        """Deprecated: ``simulate(config)`` → ``(r.lat, r.waits)``."""
        _warn_deprecated("latencies_waits", "simulate(config)")
        r = self.simulate(config)
        return r.lat, r.waits

    def qos_rate(self, config) -> float:
        """Deprecated: ``qos(config).rates``."""
        _warn_deprecated("qos_rate", "qos(config).rates")
        return self.qos(config).rates

    # --------------------------------------------------- continuous clock
    def initial_state(self) -> PoolState:
        """Idle pool at episode clock 0 — the warm-start identity element:
        every ``*_from`` entry point started here reproduces its cold
        counterpart bit for bit."""
        return PoolState.idle(self.max_instances)

    def _warm_free0(self, state: PoolState,
                    active: np.ndarray) -> np.ndarray:
        """(S,) float32 initial carry in the bound stream's local frame,
        with the horizon guard applied to arrivals and carried busy time."""
        if len(state.free) != self.max_instances:
            raise ValueError(
                f"state has {len(state.free)} slots, simulator pads to "
                f"{self.max_instances}")
        rel = np.asarray(state.free, dtype=np.float64) - float(state.clock)
        horizon = float(self.workload.arrivals[-1])
        if active.any():
            horizon = max(horizon, float(rel[active].max()))
        _check_horizon(horizon, "warm-start segment")
        return np.where(active, rel.astype(np.float32),
                        np.float32(_INF))

    def segment_from(self, state: PoolState, config, *, policy=None,
                     telemetry: bool = False) -> "SegmentResult":
        """Serve the bound stream as one continuous-time segment.

        Returns a :class:`SegmentResult` whose ``lat``/``waits`` equal the
        cold single lane bit for bit when ``state`` is the idle carry at
        clock 0, and whose ``state_at(k)`` gives the pool state after the
        first ``k`` queries — ``state_at(n_queries)`` is the scan's own
        final carry, so chaining segments reproduces the whole-stream bits
        exactly.  ``policy=`` routes dispatch (one unstacked
        :class:`RoutingPolicy`); the prefix-carry reconstruction reads the
        recorded (slot, finish) trace, so it is policy-agnostic.
        ``telemetry=True`` attaches the segment's telemetry (computed on
        the host from the recorded trace — bit-identical to the device
        accumulators, see tests/test_telemetry.py).
        """
        policy = self._check_policy(policy)
        if policy is not None and policy.stacked:
            raise ValueError("segment_from serves one pool; stacked "
                             "policies ride the batch/grid lanes")
        n = self.workload.n_queries
        total = sum(int(c) for c in config)
        if n == 0 or total == 0:
            # An empty pool serves nothing (+inf convention) and an empty
            # stream serves nothing: the carry passes through unchanged.
            return SegmentResult(
                lat=np.full(n, np.inf), waits=np.full(n, np.inf),
                _state0=state, _active=None, _rel0=None, _fin=None,
                _slots=None, _final_rel=None,
                telemetry=(Telemetry.zeros(len(self.types)) if telemetry
                           else None))
        type_of_slot, active = self._slots(config)
        free0 = self._warm_free0(state, active)
        if policy is None:
            free_f, (lat, start, slot) = _simulate_scan(
                self._arrivals, self._service, jnp.asarray(type_of_slot),
                self._priority, jnp.asarray(free0))
        else:
            pref, aff, hed = self._policy_single_args(policy, type_of_slot)
            free_f, (lat, start, slot) = _simulate_scan_policy(
                self._arrivals, self._service, jnp.asarray(type_of_slot),
                self._priority, jnp.asarray(free0), pref, aff, hed)
        lat64 = np.asarray(jax.device_get(lat), dtype=np.float64)
        start32 = np.asarray(jax.device_get(start), dtype=np.float32)
        slots = np.asarray(jax.device_get(slot))
        # Same float32-cast arrival baseline as latencies_waits, so the
        # idle-carry waits match the cold path bit for bit.
        arr = np.asarray(jax.device_get(self._arrivals), dtype=np.float64)
        waits = np.maximum(np.asarray(start32, dtype=np.float64) - arr, 0.0)
        if self._service_host is None:
            self._service_host = np.asarray(jax.device_get(self._service))
        # Per-query finish times recomputed with the same float32 add the
        # scan performed (start + service, IEEE round-to-nearest on both
        # sides), so a prefix carry matches the device's own step carry.
        svc32 = self._service_host[type_of_slot[slots], np.arange(n)]
        fin = np.asarray(start32 + svc32, dtype=np.float64)
        final_rel = np.asarray(jax.device_get(free_f), dtype=np.float64)
        seg = SegmentResult(lat=lat64, waits=waits, _state0=state,
                            _active=active, _rel0=free0.astype(np.float64),
                            _fin=fin, _slots=slots, _final_rel=final_rel,
                            _start=start32)
        if telemetry:
            seg.telemetry = self.segment_telemetry(seg, config)
        return seg

    def segment_telemetry(self, seg: "SegmentResult", config, lo: int = 0,
                          hi: int | None = None) -> Telemetry:
        """Telemetry over queries ``[lo, hi)`` of a served segment.

        Host-side, from the segment's recorded dispatch trace, with the
        device kernels' own float32 arithmetic — so a full-segment call is
        bit-identical to ``segment_from(..., telemetry=True)``'s device
        outputs, and slicing a segment into windows and merging the pieces
        reproduces the one-shot telemetry exactly (integer accumulators).
        This is what the scenario engine's per-window enrichment reads.
        """
        n = seg.n_queries
        hi = n if hi is None else int(hi)
        if not 0 <= lo <= hi <= n:
            raise ValueError(f"window [{lo}, {hi}) outside [0, {n}]")
        n_types = len(self.types)
        if seg._active is None or lo == hi:
            return Telemetry.zeros(n_types)
        type_of_slot, active = self._slots(config)
        slots = seg._slots
        tslot = type_of_slot[slots]
        if self._service_host is None:
            self._service_host = np.asarray(jax.device_get(self._service))
        svc32 = self._service_host[tslot, np.arange(n)]
        arr32 = np.asarray(jax.device_get(self._arrivals), dtype=np.float32)
        wait32 = np.maximum(seg._start - arr32, np.float32(0.0))
        depth = queue_depth(slots, seg._fin,
                            np.asarray(seg._rel0, dtype=np.float32),
                            active, arr32)
        qos_t = _qos_threshold_f32(self.model.qos_latency)
        return from_arrays(
            seg.lat[lo:hi], wait32[lo:hi], svc32[lo:hi], tslot[lo:hi],
            n_types, qos_t, depth=depth[lo:hi])

    def latencies_from(self, state: PoolState,
                       config) -> tuple[np.ndarray, PoolState]:
        """Deprecated: ``simulate(config, state=state)``."""
        _warn_deprecated("latencies_from", "simulate(config, state=state)")
        r = self.simulate(config, state=state)
        return r.lat, r.state

    def latencies_waits_from(
            self, state: PoolState,
            config) -> tuple[np.ndarray, np.ndarray, PoolState]:
        """Deprecated: ``simulate(config, state=state)``."""
        _warn_deprecated("latencies_waits_from",
                         "simulate(config, state=state)")
        r = self.simulate(config, state=state)
        return r.lat, r.waits, r.state

    def qos_rate_from(self, state: PoolState,
                      config) -> tuple[float, PoolState]:
        """Deprecated: ``qos(config, state=state)``."""
        _warn_deprecated("qos_rate_from", "qos(config, state=state)")
        r = self.qos(config, state=state)
        return r.rates, r.state

    def carried_wait(self, state: PoolState, config, at: float) -> float:
        """In-flight busy seconds carried into local time ``at``: the sum
        over the config's slots of (next-free − at), clamped at zero — the
        backlog a control-plane cut at ``at`` would have dropped under
        idle-restart segment accounting."""
        total = int(sum(int(c) for c in config))
        rel = (np.asarray(state.free[:total], dtype=np.float64)
               - float(state.clock))
        return float(np.maximum(rel - float(at), 0.0).sum())

    # ------------------------------------------------ warm batched / grid
    def _warm_free_matrix(self, state: PoolState, configs: np.ndarray,
                          deployed, now, warmup=None) -> np.ndarray:
        """(B, max_instances) float64 episode next-free matrix: candidate
        ``b``'s initial carry.  With ``deployed`` given, each row is the
        vectorized ``PoolState.remap`` of switching the live pool (currently
        ``deployed``) to ``configs[b]`` at episode time ``now`` (default:
        the local stream origin ``state.clock``), slots added by the switch
        paying their per-type ``warmup`` cold start; with ``deployed=None``
        every candidate inherits the carry slot-for-slot (no switch, no
        cold start)."""
        if len(state.free) != self.max_instances:
            raise ValueError(
                f"state has {len(state.free)} slots, simulator pads to "
                f"{self.max_instances}")
        if deployed is None:
            return np.broadcast_to(
                np.asarray(state.free, dtype=np.float64),
                (len(configs), self.max_instances))
        t_now = float(state.clock) if now is None else float(now)
        return state.remap_batch(deployed, configs, t_now, warmup=warmup)

    def _warm_free0_rows(self, state: PoolState, free_matrix: np.ndarray,
                         active: np.ndarray, horizon: float,
                         context: str) -> np.ndarray:
        """(B, S) float32 initial carries in the bound stream's local frame
        — the batched mirror of ``_warm_free0`` (same float64 subtraction,
        same float32 cast, same horizon guard), so each row is bit-identical
        to what the sequential warm path would build for that candidate."""
        rel = np.asarray(free_matrix, dtype=np.float64) - float(state.clock)
        if active.any():
            horizon = max(horizon, float(rel[active].max()))
        _check_horizon(horizon, context)
        return np.where(active, rel.astype(np.float32), np.float32(_INF))

    def _states_free0(self, states, configs, active, arrivals,
                      warmup) -> np.ndarray:
        """(W, B, S) float32 per-workload-row initial carries for the
        ``states=`` grid: row ``w`` is the same ``remap_batch`` → local-frame
        carry the shared ``state=`` path builds, from that row's own
        ``(PoolState, deployed)`` pair — or the idle carry when the entry is
        ``None`` — so each row stays bit-identical to a separate warm grid
        call on its phase carry."""
        rows = []
        for w, entry in enumerate(states):
            if entry is None:
                rows.append(_cold_free0(active))
                continue
            st, dep = entry
            mat = self._warm_free_matrix(st, configs, dep, None, warmup)
            rows.append(self._warm_free0_rows(
                st, mat, active, float(arrivals[w, -1]),
                "warm-start phase grid"))
        return np.stack(rows)

    def _sim_batch_from(self, state: PoolState, configs, policy, deployed,
                        now, warmup,
                        telemetry: bool = False) -> tuple[np.ndarray, list,
                                                          "Telemetry | None"]:
        """Warm batch core: B candidate pools served from the live backlog
        in one dispatch, plus each candidate's final carry.

        Row ``i`` is bit-identical to ``segment_from(state_i, configs[i],
        policy=policy)`` where ``state_i`` is ``state`` itself
        (``deployed=None``) or ``state.remap(deployed, configs[i], now,
        warmup)`` — the what-if carry of redeploying the live pool as
        candidate ``i`` at episode time ``now`` (default ``state.clock``,
        i.e. the bound stream's local origin), added slots paying their
        tier's ``warmup`` cold start.  The idle carry at clock 0 reproduces
        the cold batch lane bit for bit.  A stacked policy folds into the
        lane axis: ``lat`` (P, B, n_queries), states a [P][B] nested list.
        With ``telemetry`` the twin scan's outputs additionally feed the
        device finalize pass; the third element is None otherwise.
        """
        n = self.workload.n_queries
        n_b = len(configs)
        stacked = policy is not None and policy.stacked
        n_p = policy.n_policies if stacked else 1
        tel_shape = (n_p, n_b) if stacked else None
        zeros_tel = (Telemetry.zeros(len(self.types),
                                     (n_p, n_b) if stacked else (n_b,))
                     if telemetry else None)
        if configs.size == 0:
            if stacked:
                return (np.zeros((n_p, 0, n), dtype=np.float64),
                        [[] for _ in range(n_p)], zeros_tel)
            return np.zeros((0, n), dtype=np.float64), [], zeros_tel
        free_mat = self._warm_free_matrix(state, configs, deployed, now,
                                          warmup)
        type_of_slot, active = self._slots_batch(configs)
        if n == 0:
            # Empty stream: every candidate's carry passes through unchanged.
            def carries() -> list[PoolState]:
                return [PoolState(free=free_mat[b].copy(),
                                  clock=state.clock) for b in range(n_b)]

            if stacked:
                return (np.zeros((n_p, n_b, 0), dtype=np.float64),
                        [carries() for _ in range(n_p)], zeros_tel)
            return np.zeros((n_b, 0), dtype=np.float64), carries(), zeros_tel
        free0 = self._warm_free0_rows(
            state, free_mat, active, float(self.workload.arrivals[-1]),
            "warm-start batch")
        width = None
        start = packed = None
        if policy is None:
            zero = configs.sum(axis=1) == 0
            if telemetry:
                tos_d, prio, fr0_d, n_act, iota, width = self._tel_operands(
                    type_of_slot, active, free0)
                free_f, (lat, start, packed) = _scan_tel_batch(
                    self._arrivals, self._service, tos_d, prio, fr0_d,
                    n_act, iota)
            else:
                free_f, (lat, _, _) = _simulate_scan_batch(
                    self._arrivals, self._service, jnp.asarray(type_of_slot),
                    self._priority, jnp.asarray(free0))
        else:
            tos, fr0, pref, aff, hed, n_p = _fold_policy(policy,
                                                         type_of_slot, free0)
            active = np.tile(active, (n_p, 1))
            free_mat = np.tile(free_mat, (n_p, 1))
            zero = np.tile(configs.sum(axis=1) == 0, n_p)
            if telemetry:
                tos_d, prio, fr0_d, n_act, iota, width = self._tel_operands(
                    tos, active, fr0)
                free_f, (lat, start, packed) = _scan_policy_tel_batch(
                    self._arrivals, self._service, tos_d, prio, fr0_d,
                    jnp.asarray(np.ascontiguousarray(pref[:, :width])),
                    jnp.asarray(aff), jnp.asarray(hed), n_act, iota)
            else:
                free_f, (lat, _, _) = _scan_policy_batch(
                    self._arrivals, self._service, jnp.asarray(tos),
                    self._priority, jnp.asarray(fr0), jnp.asarray(pref),
                    jnp.asarray(aff), jnp.asarray(hed))
        out = np.asarray(jax.device_get(lat), dtype=np.float64)
        out[zero, :] = np.inf
        tel = None
        if telemetry:
            tel = self._tel_batch(lat, start, packed, tel_shape, zero)
        final_rel = np.asarray(jax.device_get(free_f), dtype=np.float64)
        if width is not None and width < active.shape[1]:
            # Widen the trimmed twin carry back to full slot padding; the
            # tail holds absent slots only, whose carry is never read.
            pad = np.full((len(final_rel), active.shape[1] - width), _INF)
            final_rel = np.concatenate([final_rel, pad], axis=1)
        free_out = np.where(active, final_rel + float(state.clock), free_mat)
        states = [PoolState(free=free_out[b], clock=state.clock)
                  for b in range(len(free_out))]
        if stacked:
            return (out.reshape(n_p, n_b, n),
                    [states[p * n_b:(p + 1) * n_b] for p in range(n_p)], tel)
        return out, states, tel

    def latencies_batch_from(self, state: PoolState, configs, deployed=None,
                             now=None,
                             warmup=None) -> tuple[np.ndarray,
                                                   list[PoolState]]:
        """Deprecated: ``simulate(configs, state=, deployed=, ...)``."""
        _warn_deprecated("latencies_batch_from",
                         "simulate(configs, state=, deployed=)")
        r = self.simulate(configs, state=state, deployed=deployed, now=now,
                          warmup=warmup)
        return r.lat, r.state

    def qos_rate_batch_from(self, state: PoolState, configs, deployed=None,
                            now=None,
                            warmup=None) -> tuple[np.ndarray,
                                                  list[PoolState]]:
        """Deprecated: ``qos(configs, state=, deployed=, ...)``."""
        _warn_deprecated("qos_rate_batch_from",
                         "qos(configs, state=, deployed=)")
        r = self.qos(configs, state=state, deployed=deployed, now=now,
                     warmup=warmup)
        return r.rates, r.state

    def latencies_grid_from(self, state: PoolState, configs, load_factors,
                            service_tables=None, deployed=None,
                            now=None, warmup=None) -> np.ndarray:
        """Deprecated: ``simulate(configs, workloads=, state=, ...)``."""
        _warn_deprecated("latencies_grid_from",
                         "simulate(configs, workloads=, state=)")
        return self.simulate(configs, workloads=load_factors,
                             service_tables=service_tables, state=state,
                             deployed=deployed, now=now, warmup=warmup).lat

    def qos_rate_grid_from(self, state: PoolState, configs, load_factors,
                           service_tables=None, deployed=None,
                           now=None, warmup=None) -> np.ndarray:
        """Deprecated: ``qos(configs, workloads=, state=, ...)``."""
        _warn_deprecated("qos_rate_grid_from",
                         "qos(configs, workloads=, state=)")
        return self.qos(configs, workloads=load_factors,
                        service_tables=service_tables, state=state,
                        deployed=deployed, now=now, warmup=warmup).rates

    # ------------------------------------------------------------- batched
    def _tel_operands(self, tos, active, free0) -> tuple:
        """Occupancy-trimmed device operands for the telemetry twin scans:
        (type_of_slot, priority, free0, n_active, iota, width).  Active
        slots are packed in the ``[0, total)`` prefix, so trimming the
        padded tail (same power-of-two sizing as the grid sweep) changes no
        dispatch decision.  The width-keyed constants are cached — the
        twin lanes are benched against the legacy kernels at ≤10 %
        overhead, so per-call host work stays minimal."""
        totals = active.sum(axis=1)
        width = self._grid_slot_pad(totals)
        cache = getattr(self, "_tel_width_cache", None)
        if cache is None:
            cache = self._tel_width_cache = {}
        ent = cache.get(width)
        if ent is None:
            ent = cache[width] = (self._priority[:width],
                                  jnp.arange(width, dtype=jnp.int32))
        return (jnp.asarray(np.ascontiguousarray(tos[:, :width])), ent[0],
                jnp.asarray(np.ascontiguousarray(free0[:, :width])),
                jnp.asarray(totals.astype(np.int32)), ent[1], width)

    def _tel_batch(self, lat, start, packed, tel_shape, zero) -> Telemetry:
        """Run the device telemetry finalize over one twin-scan batch
        dispatch's outputs and assemble the host :class:`Telemetry`
        (``tel_shape`` unfolds a stacked-policy lane axis)."""
        parts = _tel_finalize_batch(
            lat, start, packed, self._arrivals, self._service,
            jnp.float32(_qos_threshold_f32(self.model.qos_latency)),
            _edges_dev())
        return _device_telemetry(parts, len(self.types), zero=zero,
                                 shape=tel_shape)

    def _sim_batch(self, configs, policy,
                   telemetry: bool = False) -> tuple[np.ndarray,
                                                     Telemetry | None]:
        """Cold batch core: per-query latencies for a (B, n_types) batch in
        one dispatch — (B, n_queries) float64, rows of all-zero configs
        +inf (no pool, every query violates).  Row ``i`` equals the single
        lane on ``configs[i]`` bit for bit.  A stacked policy folds P·B
        lanes into the dispatch and returns (P, B, n_queries).  With
        ``telemetry`` the twin scan's outputs feed the device finalize pass
        (see ``_tel_finalize``); without it the second element is None."""
        n = self.workload.n_queries
        n_b = len(configs)
        stacked = policy is not None and policy.stacked
        n_p = policy.n_policies if stacked else 1
        tel_shape = (n_p, n_b) if stacked else None
        if configs.size == 0 or n == 0:
            if configs.size:
                self._slots_batch(configs)  # keep shape/padding validation
            shape = (n_p, n_b, n) if stacked else (n_b, n)
            tel = None
            if telemetry:
                tel = Telemetry.zeros(len(self.types), shape[:-1])
            return np.zeros(shape, dtype=np.float64), tel
        with tracing.span("sim.stage", self.request, lane="batch"):
            type_of_slot, active = self._slots_batch(configs)
            free0 = _cold_free0(active)
            start = packed = None
            if policy is None:
                zero = configs.sum(axis=1) == 0
                if telemetry:
                    tos_d, prio, fr0_d, n_act, iota, _ = self._tel_operands(
                        type_of_slot, active, free0)
                    _, (lat, start, packed) = _scan_tel_batch(
                        self._arrivals, self._service, tos_d, prio, fr0_d,
                        n_act, iota)
                else:
                    _, (lat, _, _) = _simulate_scan_batch(
                        self._arrivals, self._service,
                        jnp.asarray(type_of_slot), self._priority,
                        jnp.asarray(free0))
            else:
                tos, fr0, pref, aff, hed, n_p = _fold_policy(
                    policy, type_of_slot, free0)
                zero = np.tile(configs.sum(axis=1) == 0, n_p)
                if telemetry:
                    active_l = np.tile(active, (n_p, 1))
                    tos_d, prio, fr0_d, n_act, iota, width = (
                        self._tel_operands(tos, active_l, fr0))
                    _, (lat, start, packed) = _scan_policy_tel_batch(
                        self._arrivals, self._service, tos_d, prio, fr0_d,
                        jnp.asarray(np.ascontiguousarray(pref[:, :width])),
                        jnp.asarray(aff), jnp.asarray(hed), n_act, iota)
                else:
                    _, (lat, _, _) = _scan_policy_batch(
                        self._arrivals, self._service, jnp.asarray(tos),
                        self._priority, jnp.asarray(fr0), jnp.asarray(pref),
                        jnp.asarray(aff), jnp.asarray(hed))
        out = np.asarray(_fetch(lat, "batch", self.request),
                         dtype=np.float64)
        out[zero, :] = np.inf
        if stacked:
            out = out.reshape(n_p, n_b, n)
        tel = None
        if telemetry:
            tel = self._tel_batch(lat, start, packed, tel_shape, zero)
        return out, tel

    def latencies_batch(self, configs) -> np.ndarray:
        """Deprecated: ``simulate(configs).lat``."""
        _warn_deprecated("latencies_batch", "simulate(configs).lat")
        return self.simulate(configs).lat

    def qos_rate_batch(self, configs) -> np.ndarray:
        """Deprecated: ``qos(configs).rates``."""
        _warn_deprecated("qos_rate_batch", "qos(configs).rates")
        return self.qos(configs).rates

    # ---------------------------------------------------------------- grid
    def _stacked_arrivals(self, load_factors) -> np.ndarray:
        """(W, n_queries) float64 arrival grid for ``workload.scaled`` levels.

        Division happens in float64 *before* the float32 device cast, exactly
        as a ``PoolSimulator`` bound to ``workload.scaled(f)`` would see its
        arrivals — the root of the grid's per-cell bit-identity.
        """
        factors = np.asarray(load_factors, dtype=np.float64)
        if factors.ndim != 1 or factors.size == 0:
            raise ValueError("load_factors must be a non-empty 1-D sequence")
        if (factors <= 0).any() or not np.isfinite(factors).all():
            raise ValueError("load factors must be finite and > 0")
        base = np.asarray(self.workload.arrivals, dtype=np.float64)
        out = base[None, :] / factors[:, None]
        if out.size:
            _check_horizon(float(out[:, -1].max()), "load-factor grid")
        return out

    def _stacked_service(self, service_tables, n_w: int):
        """Validate + device-cast an optional (W, n_types, n_queries) stack
        of per-workload service tables (float64 in, float32 on device — the
        same cast the bound table receives, so a row reproduces a simulator
        built on that batch stream bit for bit)."""
        if service_tables is None:
            return None
        tables = np.asarray(service_tables, dtype=np.float64)
        expect = (n_w, len(self.types), self.workload.n_queries)
        if tables.shape != expect:
            raise ValueError(f"service_tables must have shape {expect} "
                             f"(W, n_types, n_queries), got {tables.shape}")
        return jnp.asarray(tables, dtype=jnp.float32)

    def _sim_grid(self, configs, load_factors, service_tables, policy,
                  state, deployed, now, warmup,
                  telemetry: bool = False) -> tuple[np.ndarray,
                                                    "Telemetry | None"]:
        """Grid core: per-query latencies on the (workload × config) grid,
        one dispatch — (W, B, n_queries) float64 where cell ``[w, b]``
        equals ``PoolSimulator(..., workload.scaled(load_factors[w]))`` on
        the single lane for ``configs[b]`` bit for bit (all-zero config
        rows +inf), cold from idle or warm from ``state`` (per-candidate
        ``remap`` exactly as the batch lane; backlog is wall-clock, so one
        (B, S) carry serves every workload row).  ``service_tables``
        (optional, (W, n_types, n_queries)) gives each workload row its own
        table — the batch-distribution axis.  A stacked policy folds into
        the lane axis and returns (W, P, B, n_queries).  With ``telemetry``
        the scan outputs feed the grid finalize pass (leading dims (W,
        [P,] B)); the second element is None otherwise."""
        arrivals = self._stacked_arrivals(load_factors)
        n_w = len(arrivals)
        n = self.workload.n_queries
        n_b = len(configs)
        tables = self._stacked_service(service_tables, n_w)
        stacked = policy is not None and policy.stacked
        n_p = policy.n_policies if stacked else 1
        tel_shape = (n_w, n_p, n_b) if stacked else None
        if configs.size == 0 or n == 0:
            if configs.size:
                self._slots_batch(configs)  # keep shape/padding validation
            shape = ((n_w, n_p, n_b, n) if stacked else (n_w, n_b, n))
            tel = None
            if telemetry:
                tel = Telemetry.zeros(len(self.types), shape[:-1])
            return np.zeros(shape, dtype=np.float64), tel
        type_of_slot, active = self._slots_batch(configs)
        if state is None:
            free0 = _cold_free0(active)
        else:
            free_mat = self._warm_free_matrix(state, configs, deployed, now,
                                              warmup)
            free0 = self._warm_free0_rows(
                state, free_mat, active, float(arrivals[:, -1].max()),
                "warm-start grid")
        arr_dev = jnp.asarray(arrivals, jnp.float32)
        svc = self._service if tables is None else tables
        start = packed = None
        if policy is None:
            zero = configs.sum(axis=1) == 0
            if telemetry:
                tos_d, prio, fr0_d, n_act, iota, _ = self._tel_operands(
                    type_of_slot, active, free0)
                kernel = (_scan_tel_grid if tables is None
                          else _scan_tel_grid_tables)
                _, (lat, start, packed) = kernel(
                    arr_dev, svc, tos_d, prio, fr0_d, n_act, iota)
            else:
                kernel = (_simulate_scan_grid if tables is None
                          else _simulate_scan_grid_tables)
                _, (lat, _, _) = kernel(
                    arr_dev, svc, jnp.asarray(type_of_slot),
                    self._priority, jnp.asarray(free0))
        else:
            tos, fr0, pref, aff, hed, n_p = _fold_policy(policy,
                                                         type_of_slot, free0)
            zero = np.tile(configs.sum(axis=1) == 0, n_p)
            if telemetry:
                active_l = np.tile(active, (n_p, 1))
                tos_d, prio, fr0_d, n_act, iota, width = self._tel_operands(
                    tos, active_l, fr0)
                kernel = (_scan_policy_tel_grid if tables is None
                          else _scan_policy_tel_grid_tables)
                _, (lat, start, packed) = kernel(
                    arr_dev, svc, tos_d, prio, fr0_d,
                    jnp.asarray(np.ascontiguousarray(pref[:, :width])),
                    jnp.asarray(aff), jnp.asarray(hed), n_act, iota)
            else:
                kernel = (_scan_policy_grid if tables is None
                          else _scan_policy_grid_tables)
                _, (lat, _, _) = kernel(
                    arr_dev, svc, jnp.asarray(tos), self._priority,
                    jnp.asarray(fr0), jnp.asarray(pref), jnp.asarray(aff),
                    jnp.asarray(hed))
        out = np.asarray(jax.device_get(lat), dtype=np.float64)
        out[:, zero, :] = np.inf
        tel = None
        if telemetry:
            fin_jit = (_tel_finalize_grid if tables is None
                       else _tel_finalize_grid_tables)
            parts = fin_jit(
                lat, start, packed, arr_dev, svc,
                jnp.float32(_qos_threshold_f32(self.model.qos_latency)),
                _edges_dev())
            tel = _device_telemetry(parts, len(self.types), zero=zero,
                                    shape=tel_shape)
        if stacked:
            out = out.reshape(n_w, n_p, n_b, n)
        return out, tel

    def latencies_grid(self, configs, load_factors,
                       service_tables=None) -> np.ndarray:
        """Deprecated: ``simulate(configs, workloads=...).lat``."""
        _warn_deprecated("latencies_grid",
                         "simulate(configs, workloads=...).lat")
        return self.simulate(configs, workloads=load_factors,
                             service_tables=service_tables).lat

    def _grid_slot_pad(self, totals: np.ndarray) -> int:
        """Occupancy-trimmed slot padding: smallest power of two covering the
        largest pool in the batch (>= 8 so tiny batches share an executable),
        capped at ``max_instances``.  Inactive slots never win the dispatch
        argmin, so trimming them is invisible to the results."""
        need = max(int(totals.max(initial=1)), 1)
        width = max(8, 1 << (need - 1).bit_length())
        return min(width, self.max_instances)

    def _qos_grid_issue(self, configs, load_factors, *, service_tables=None,
                        policy=None, state=None, states=None, deployed=None,
                        now=None, warmup=None,
                        telemetry: bool = False) -> _GridPending:
        """``qos(configs, workloads=load_factors, ...)``'s grid lane up to
        its dispatch: validates, stages and dispatches, and returns the
        pending dispatch that ``_qos_grid_fetch`` turns into the rates —
        (W, B) float64, or (W, P, B) under a stacked policy, where cell
        ``[w, b]`` equals ``PoolSimulator(...,
        workload.scaled(load_factors[w]))``'s single-lane rate for
        ``configs[b]`` exactly.  This is the fused fast path: the lean
        count scan (see ``_grid_lane_qos_counts``) over nested (workload,
        config) axes, sharded across XLA host devices when several are
        configured, with only the int32 counts crossing back to the host.

        Nothing here waits on the device, so a caller may issue several
        dispatches before fetching the first (``PoolEvaluator``'s sweep
        does): the host stages the next while the device scans.  No host
        buffer handed to a dispatch is written again.

        ``service_tables`` (optional, (W, n_types, n_queries)) stacks one
        service table per workload row — phases with *different batch
        distributions* share the dispatch.  Warm carries (``state=``)
        remap per candidate exactly as the batch lane; the rounded-down
        float32 threshold (see ``_qos_threshold_f32``) keeps device counts
        bit-compatible with the host comparison either way.

        With ``telemetry`` the sweep runs the in-carry accumulator kernels
        (``_grid_lane_qos_counts_tel``): same dispatch recurrence, same
        count arithmetic, constant memory — only the counters cross back to
        the host.  That twin fetches at once, and its telemetry rides the
        pending dispatch.
        """
        policy = self._check_policy(policy)
        if states is not None:
            if state is not None or deployed is not None or now is not None:
                raise ValueError("states= carries its own (state, deployed) "
                                 "pairs; state=/deployed=/now= do not apply")
            if telemetry:
                raise ValueError("telemetry is not supported on the "
                                 "per-row states= grid")
        else:
            self._check_warm_kwargs(state, deployed, now, warmup)
        configs = np.asarray(configs, dtype=np.int64)
        if configs.ndim != 2:
            raise ValueError("the workload grid needs a (B, n_types) "
                             "config batch")
        with tracing.span("sim.stage", self.request, lane="grid"):
            arrivals = self._stacked_arrivals(load_factors)
            n_w = len(arrivals)
            n_b = len(configs)
            tables = self._stacked_service(service_tables, n_w)
            stacked = policy is not None and policy.stacked
            n_p = policy.n_policies if stacked else 1
            shape = (n_w, n_p, n_b) if stacked else (n_w, n_b)
            if configs.size == 0 or self.workload.n_queries == 0:
                if configs.size:
                    # Keep shape/padding validation.
                    self._slots_batch(configs)
                tel = (Telemetry.zeros(len(self.types), shape)
                       if telemetry else None)
                if self.workload.n_queries == 0 and configs.size:
                    # 0/0 convention: an empty stream has no violations.
                    rates = np.full(shape, np.nan, dtype=np.float64)
                else:
                    rates = np.zeros(shape, dtype=np.float64)
                return _GridPending(None, n_w, n_p * n_b, shape, rates, tel)
            type_of_slot, active = self._slots_batch(configs)
            if states is not None:
                if len(states) != n_w:
                    raise ValueError(f"states= needs one entry per workload "
                                     f"row ({n_w}), got {len(states)}")
                free0 = self._states_free0(states, configs, active,
                                           arrivals, warmup)
            elif state is None:
                free0 = _cold_free0(active)
            else:
                free_mat = self._warm_free_matrix(state, configs, deployed,
                                                  now, warmup)
                free0 = self._warm_free0_rows(
                    state, free_mat, active, float(arrivals[:, -1].max()),
                    "warm-start grid")
        if telemetry:
            counts, tel = self._qos_counts_grid_tel(
                arrivals, tables, type_of_slot, free0, configs, policy,
                shape if stacked else None)
            rates = counts.astype(np.float64) / self.workload.n_queries
            return _GridPending(None, n_w, n_p * n_b, shape,
                                rates.reshape(shape), tel)
        counts = self._qos_counts_grid(arrivals, tables, type_of_slot, free0,
                                       configs, load_factors, policy)
        return _GridPending(counts, n_w, n_p * n_b, shape)

    def _qos_grid_fetch(self, pending: _GridPending,
                        queued: int = 0) -> np.ndarray:
        """The rates of a pending grid dispatch: waits for its counts
        (span ``sim.wait``, lane ``grid``; ``queued`` is the number of
        other dispatches its caller issued and has not fetched yet), keeps
        their real block and divides by the stream's length."""
        if pending.counts is None:
            return pending.rates
        counts = np.asarray(_fetch(pending.counts, "grid", self.request,
                                   queued=queued))
        rates = (counts[:pending.n_w, :pending.n_l].astype(np.float64)
                 / self.workload.n_queries)
        return rates.reshape(pending.shape)

    def qos_rate_grid(self, configs, load_factors,
                      service_tables=None) -> np.ndarray:
        """Deprecated: ``qos(configs, workloads=...).rates``."""
        _warn_deprecated("qos_rate_grid", "qos(configs, workloads=...).rates")
        return self.qos(configs, workloads=load_factors,
                        service_tables=service_tables).rates

    def _qos_counts_grid(self, arrivals, tables, type_of_slot, free0_rows,
                         configs, load_factors, policy=None) -> jax.Array:
        """One fused (W, L) QoS-count sweep from per-config initial carries
        (``free0_rows``: (B, max_instances) float32, or (W, B, max_instances)
        for the per-row ``states=`` grid) — the shared dispatch behind the
        cold (idle carries) and warm (live carries) grid lanes, so both ride
        the identical executables.  With ``policy`` the lane axis is the
        policy fold (L = P·B).  Every flavor — plain, stacked-table, routed,
        and both combined — shards across the host devices through
        ``_dispatch_grid_sharded`` when several are configured; the per-row
        ``states=`` carries run the single-device states jits.  Returns the
        device counts, unfetched (the sharded path's hold pad rows or
        lanes past the real (W, L) block)."""
        with tracing.span("sim.stage", self.request, lane="grid"):
            width = self._grid_slot_pad(configs.sum(axis=1))
            arr = np.asarray(arrivals, np.float32)                # (W, nq)
            tos = np.ascontiguousarray(type_of_slot[:, :width])   # (B, S)
            free0 = np.ascontiguousarray(free0_rows[..., :width])
            per_row = free0.ndim == 3                             # (W, B, S)

            qos_t = jnp.float32(_qos_threshold_f32(self.model.qos_latency))
            iota = jnp.arange(width, dtype=jnp.int32)
            policy_ops = None
            if policy is not None:
                if per_row:
                    # Fold the policy over the layout alone, then tile every
                    # row's carries across the policy axis (the carry does
                    # not depend on the policy).
                    tos2, _, pref, aff, hed, n_p = _fold_policy(
                        policy, tos, np.zeros_like(tos, dtype=np.float32))
                    free0 = np.ascontiguousarray(np.tile(free0, (1, n_p, 1)))
                    tos = tos2
                else:
                    tos, free0, pref, aff, hed, _ = _fold_policy(policy, tos,
                                                                 free0)
                policy_ops = (np.asarray(pref), np.asarray(aff),
                              np.asarray(hed))
            n_dev = jax.local_device_count()
            counts = None
            if per_row:
                ops = (jnp.asarray(arr),
                       self._service.T if tables is None
                       else jnp.transpose(tables, (0, 2, 1)),
                       jnp.asarray(tos), self._priority[:width],
                       jnp.asarray(free0), iota, qos_t)
                if policy is not None:
                    ops = ops + tuple(jnp.asarray(x) for x in policy_ops)
                    fn = (_grid_counts_policy_states_jit if tables is None
                          else _grid_counts_policy_tables_states_jit)
                else:
                    fn = (_grid_counts_states_jit if tables is None
                          else _grid_counts_tables_states_jit)
                counts, _ = fn(*ops)
            elif n_dev > 1:
                factors = tuple(float(f) for f in np.asarray(load_factors,
                                                             dtype=np.float64))
            elif policy is not None:
                pref, aff, hed = (jnp.asarray(x) for x in policy_ops)
                if tables is not None:
                    counts, _ = _grid_counts_policy_tables_jit(
                        jnp.asarray(arr), jnp.transpose(tables, (0, 2, 1)),
                        jnp.asarray(tos), self._priority[:width],
                        jnp.asarray(free0), iota, qos_t, pref, aff, hed)
                else:
                    counts, _ = _grid_counts_policy_jit(
                        jnp.asarray(arr), self._service.T, jnp.asarray(tos),
                        self._priority[:width], jnp.asarray(free0), iota,
                        qos_t, pref, aff, hed)
            elif tables is not None:
                counts, _ = _grid_counts_tables_jit(
                    jnp.asarray(arr), jnp.transpose(tables, (0, 2, 1)),
                    jnp.asarray(tos), self._priority[:width],
                    jnp.asarray(free0), iota, qos_t)
            else:
                counts, _ = _grid_counts_jit(
                    jnp.asarray(arr), self._service.T, jnp.asarray(tos),
                    self._priority[:width], jnp.asarray(free0), iota, qos_t)
        if counts is None:
            return self._dispatch_grid_sharded(arr, tables, tos, free0,
                                               width, n_dev, factors,
                                               policy_ops)
        return counts

    def _qos_counts_grid_tel(self, arrivals, tables, type_of_slot,
                             free0_rows, configs, policy,
                             tel_shape) -> tuple[np.ndarray, Telemetry]:
        """Telemetry twin of ``_qos_counts_grid``: the in-carry accumulator
        kernels over the same trimmed layout.  Single-device executable only
        (the shard_map path stays telemetry-off); the QoS counts come from
        the identical dispatch recurrence and comparison, so the rates are
        bit-identical to the lean sweep's."""
        width = self._grid_slot_pad(configs.sum(axis=1))
        arr = np.asarray(arrivals, np.float32)                # (W, nq)
        tos = np.ascontiguousarray(type_of_slot[:, :width])   # (B, S)
        free0 = np.ascontiguousarray(free0_rows[:, :width])
        n_active = configs.sum(axis=1).astype(np.int32)
        zero = n_active == 0

        qos_t = jnp.float32(_qos_threshold_f32(self.model.qos_latency))
        iota = jnp.arange(width, dtype=jnp.int32)
        iota_t = jnp.arange(len(self.types), dtype=jnp.int32)
        iota_k = jnp.arange(N_BUCKETS, dtype=jnp.int32)
        edges = _edges_dev()
        if policy is not None:
            tos, free0, pref, aff, hed, n_p = _fold_policy(policy, tos,
                                                           free0)
            n_active = np.tile(n_active, n_p)
            zero = np.tile(zero, n_p)
            lane = (jnp.asarray(tos), self._priority[:width],
                    jnp.asarray(free0), iota, qos_t,
                    jnp.asarray(n_active), iota_t, iota_k, edges,
                    jnp.asarray(pref), jnp.asarray(aff), jnp.asarray(hed))
            if tables is not None:
                out = _grid_counts_policy_tel_tables_jit(
                    jnp.asarray(arr), jnp.transpose(tables, (0, 2, 1)),
                    *lane)
            else:
                out = _grid_counts_policy_tel_jit(
                    jnp.asarray(arr), self._service.T, *lane)
        else:
            lane = (jnp.asarray(tos), self._priority[:width],
                    jnp.asarray(free0), iota, qos_t,
                    jnp.asarray(n_active), iota_t, iota_k, edges)
            if tables is not None:
                out = _grid_counts_tel_tables_jit(
                    jnp.asarray(arr), jnp.transpose(tables, (0, 2, 1)),
                    *lane)
            else:
                out = _grid_counts_tel_jit(
                    jnp.asarray(arr), self._service.T, *lane)
        counts = np.asarray(jax.device_get(out[0]))
        tel = _device_telemetry(out[1:], len(self.types), zero=zero,
                                shape=tel_shape)
        return counts, tel

    def _grid_replicated_consts(self, width: int, n_dev: int) -> tuple:
        """Mesh-replicated sweep constants (service table, priority, slot
        iota, QoS threshold), uploaded once and cached.  shard_map under jit
        takes global operands, so "replicated" here is a ``P()`` placement
        on the lane mesh — each device reads the same buffer."""
        key = (n_dev, width)
        if key not in self._grid_consts:
            rep = NamedSharding(_lane_mesh(n_dev), P())
            self._grid_consts[key] = (
                jax.device_put(self._service.T, rep),
                jax.device_put(self._priority[:width], rep),
                jax.device_put(jnp.arange(width, dtype=jnp.int32), rep),
                jax.device_put(
                    jnp.float32(_qos_threshold_f32(self.model.qos_latency)),
                    rep),
            )
        return self._grid_consts[key]

    def _grid_arr_shards(self, arr: np.ndarray, mode: str, n_dev: int,
                         factors: tuple) -> jnp.ndarray:
        """Device layout of the (W, nq) arrival grid, LRU-cached per
        load-factor tuple: workload-axis lane shards ("w", cyclically padded
        with duplicate levels to a device multiple) or a mesh-replicated
        buffer ("b").  Hits refresh recency, so a rescale loop cycling
        through more monitored-level sets than the cache holds evicts the
        stalest set instead of thrashing re-uploads of the ones it keeps
        re-sweeping."""
        key = (mode, n_dev, factors)
        out = self._grid_arrs.pop(key, None)
        if out is None:
            mesh = _lane_mesh(n_dev)
            if mode == "w":
                n_w = len(arr)
                pad_w = (-n_w) % n_dev
                if pad_w:
                    # Cyclic padding: pad_w may exceed n_w (e.g. one load
                    # level on an 8-device host), so wrap the row index.
                    arr = np.concatenate(
                        [arr, arr[np.arange(pad_w) % n_w]])
                out = jax.device_put(jnp.asarray(arr),
                                     NamedSharding(mesh, P("lane")))
            else:
                out = jax.device_put(jnp.asarray(arr),
                                     NamedSharding(mesh, P()))
            while len(self._grid_arrs) >= 8:
                self._grid_arrs.pop(next(iter(self._grid_arrs)))
        # (Re-)inserting moves the key to the recent end of the dict.
        self._grid_arrs[key] = out
        return out

    def _dispatch_grid_sharded(self, arr, tables, tos, free0, width, n_dev,
                               factors, policy_ops=None) -> jax.Array:
        """One shard_mapped sweep across the lane mesh — every grid flavor
        (plain / stacked-table / routed / both).

        Splits the workload axis (cyclically padded with duplicate levels
        when it does not divide) unless the lane axis divides more cleanly —
        e.g. a single-level sweep over many configs or a wide policy fold.
        The shard_mapped executable takes global operands (no per-device
        leading axis) and per-device blocks run the same per-lane vmap
        bodies as the single-device jits, so counts are bit-identical to
        them.  Returns the device counts, unfetched, pad rows or lanes
        included: the fetch keeps the real (W, L) block.
        """
        n_w, n_b = len(arr), len(tos)
        with tracing.span("sim.stage", self.request, lane="grid"):
            service_r, prio_r, iota_r, qos_r = self._grid_replicated_consts(
                width, n_dev)
            if tables is None:
                flavor = "plain" if policy_ops is None else "policy"
                svc = service_r
            else:
                flavor = "tables" if policy_ops is None else "policy_tables"
                svc = jnp.transpose(tables, (0, 2, 1))

            # Split whichever axis wastes fewer lanes per device; both axes
            # pad cyclically (duplicate levels / duplicate lanes, results of
            # the pad rows dropped), so neither split requires exact
            # divisibility.
            pad_w = (-n_w) % n_dev
            pad_b = (-n_b) % n_dev
            lanes_w_split = ((n_w + pad_w) // n_dev) * n_b
            lanes_b_split = n_w * ((n_b + pad_b) // n_dev)
            extra = () if policy_ops is None else policy_ops
            split_b = lanes_b_split < lanes_w_split
            if split_b:
                if pad_b:
                    idx = np.arange(n_b + pad_b) % n_b
                    tos, free0 = tos[idx], free0[idx]
                    # Policy operands (pref rows, affinity, hedge) all carry
                    # the lane axis leading, so they pad with the same
                    # cyclic index.
                    extra = tuple(x[idx] for x in extra)
            elif pad_w and tables is not None:
                idx = np.arange(n_w + pad_w) % n_w
                svc = jnp.concatenate([svc, svc[idx[n_w:]]])
            mode = "b" if split_b else "w"
            fn = _sharded_counts_fn(n_dev, flavor, mode)
            counts, _ = fn(
                self._grid_arr_shards(arr, mode, n_dev, factors), svc,
                jnp.asarray(tos), prio_r, jnp.asarray(free0), iota_r, qos_r,
                *(jnp.asarray(x) for x in extra))
        return counts


@dataclass(frozen=True)
class StreamResult:
    """Outcome of a streamed QoS evaluation."""

    rate: float          # QoS satisfaction fraction (paper Eq. 2 R_sat)
    n_queries: int       # queries streamed
    rebases: int         # clock rebases taken (0 while horizon < _MAX_HORIZON/2)


class StreamingSimulator:
    """Constant-memory streamed twin of :class:`PoolSimulator`'s QoS lane.

    Bound to a generative :class:`WorkloadSpec` instead of a finite
    :class:`Workload`: query blocks are drawn on device chunk by chunk
    (``spec.generate_chunk``), each block scanned through the donated-carry
    streaming kernel (``_stream_chunk``), so evaluating ``n`` queries holds
    one block plus two carry buffers regardless of ``n``.

    Bit-exactness contract (tests/test_streaming.py):

      * while the unscaled horizon stays below ``_MAX_HORIZON / 2`` the
        streamed QoS count equals ``PoolSimulator(model, types,
        spec.realize(n)).qos(config)`` bit for bit — same layout expansion
        (``_expand_slots``), same slot-pad width, same f32 threshold
        rounding, same per-query arithmetic (the LUT gather reproduces the
        host service-table column exactly);
      * beyond that the stream *rebases*: the carry and arrival origin
        shift back to ~0 between chunks (exact f32 subtraction of the new
        origin), which keeps every in-scan timestamp inside the guarded
        float32 envelope at any episode length — the monolithic path would
        raise its horizon guard instead.
    """

    def __init__(self, model: ModelProfile, types: list[InstanceType],
                 spec: WorkloadSpec, max_instances: int = 40):
        self.model = model
        self.types = list(types)
        self.spec = spec
        self.max_instances = max_instances
        # Bucketed specs (workload.BucketedWorkloadSpec) expand the LUT to
        # one block per bucket; the kernel is unchanged — the gather index
        # becomes ``bucket * (max_batch + 1) + batch``, which with a single
        # unit bucket is just the batch size over the legacy table.
        buckets = getattr(spec, "buckets", None)
        if buckets is None:
            lut = service_time_lut(model, self.types, spec.max_batch)
        else:
            lut = bucketed_service_time_lut(model, self.types,
                                            spec.max_batch, buckets)
        self._bucketed = buckets is not None
        self._lut_stride = int(spec.max_batch) + 1
        # f32 cast *before* the transpose so lut_T rows hold exactly the
        # f32 values the monolithic path's service-table cast produces.
        self._lut_T = jnp.asarray(np.asarray(lut, dtype=np.float32).T)
        self._priority = jnp.arange(max_instances, dtype=jnp.float32)
        self.request = tracing.new_request()

    def qos(self, config, n_queries: int, *, probe=None) -> StreamResult:
        """Stream ``n_queries`` of the bound spec through ``config``.

        ``probe``, if given, is called as ``probe(chunk_index)`` after each
        block — the constant-memory bench hooks live-buffer accounting in
        here without the simulator growing a telemetry dependency.
        """
        cfg = np.asarray(config, dtype=np.int64)
        if cfg.ndim != 1 or len(cfg) != len(self.types):
            raise ValueError(f"expected ({len(self.types)},) config, got "
                             f"shape {cfg.shape}")
        n = int(n_queries)
        if n < 0:
            raise ValueError("n_queries must be >= 0")
        if n == 0:
            # 0/0 convention of the grid lane: no queries, no violations.
            return StreamResult(rate=float("nan"), n_queries=0, rebases=0)
        if int(cfg.sum()) == 0:
            # Single-lane convention: an empty pool serves nothing within
            # QoS (latencies are +inf).
            return StreamResult(rate=0.0, n_queries=n, rebases=0)
        type_of_slot, active = _expand_slots(cfg[None, :], len(self.types),
                                             self.max_instances)
        width = min(max(8, 1 << (int(cfg.sum()) - 1).bit_length()),
                    self.max_instances)
        tos = jnp.asarray(np.ascontiguousarray(type_of_slot[0, :width]))
        prio = self._priority[:width]
        iota = jnp.arange(width, dtype=jnp.int32)
        qos_t = jnp.float32(_qos_threshold_f32(self.model.qos_latency))
        free = jnp.asarray(
            np.ascontiguousarray(_cold_free0(active[0, :width])))
        count = jnp.zeros((), dtype=jnp.int32)
        full_valid = np.ones(self.spec.chunk, dtype=bool)

        chunk = self.spec.chunk
        scale = float(self.spec.scale)
        base = 0.0
        shift = 0.0
        rebases = 0
        for c in range(math.ceil(n / chunk)):
            # The host's part of a chunk: the device has nothing queued
            # until the chunk's scan is dispatched.
            with tracing.span("sim.stream_draw", self.request):
                if self._bucketed:
                    arr, local, batches, bucket = self.spec.generate_chunk(
                        c, base)
                    batches = bucket * self._lut_stride + batches
                else:
                    arr, local, batches = self.spec.generate_chunk(c, base)
                left = n - c * chunk
                if left >= chunk:
                    valid = full_valid
                else:
                    valid = np.zeros(chunk, dtype=bool)
                    valid[:left] = True
                free, count = _stream_chunk_jit(
                    free, count, jnp.float32(shift), arr, batches,
                    jnp.asarray(valid), self._lut_T, tos, prio, iota, qos_t)
            shift = 0.0
            base = float(local[-1])
            horizon = base / scale
            if horizon > _MAX_HORIZON:
                raise ValueError(
                    f"stream chunk spans {horizon:.0f}s of simulated time "
                    f"(> {_MAX_HORIZON:.0f}s): one block outruns the "
                    f"float32 envelope; raise rate_qps or shrink chunk")
            if horizon > _MAX_HORIZON / 2.0:
                # Rebase: the next chunk's gaps accumulate from 0 again,
                # and the carry drops the same origin (exact f32 value of
                # the *scaled* origin) on entry to the next block.
                shift = float(np.float32(np.float64(base) /
                              np.float64(scale)))
                base = 0.0
                rebases += 1
            if probe is not None:
                probe(c)
        count = int(_fetch(count, "stream", self.request))
        return StreamResult(rate=count / n,
                            n_queries=n, rebases=rebases)
