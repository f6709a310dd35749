"""With the timed path broken underneath, a run of each cell reports
``correct`` false: the harness's look for a chip is skipped, and the rest
of a run (set-up, one unit, the comparison with the reference) is driven
as on the chip, at the cell's own size.

Faults, each planted in the program where it would arise:

* ``state_unchanged``: a scan step returns its carry unchanged (the
  next-free time of the instance it dispatched to is not advanced);
* ``half_mean``: half of the batch is left out and the mean is taken over
  the rest (the QoS rate of half the queries of a single-config scan, or
  half the lanes of a grid dispatch standing in for the other half);
* ``answer_altered``: each QoS rate is lowered by 0.01 where it is made;
* ``exchange_left_out`` (four devices): the lanes computed on the other
  devices never come back, and the first device's stand in for them.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.run import ROOT

_BIG = 1e6    # the simulator's idle-slot priority offset


def _step(free, arrival, svc_by_type, type_of_slot, priority):
    key = jnp.where(free <= arrival, priority - _BIG, free)
    slot = jnp.argmin(key)
    start = jnp.maximum(arrival, free[slot])
    return slot, start, start + svc_by_type[type_of_slot[slot]]


def _scan_unchanged(arrivals, service, type_of_slot, priority, free0):
    def step(free, inputs):
        arrival, svc = inputs
        slot, start, finish = _step(free, arrival, svc, type_of_slot,
                                    priority)
        return free, (finish - arrival, start, slot)
    return jax.lax.scan(step, free0, (arrivals, service.T))


def _counts_unchanged(arrivals, service_T, type_of_slot, priority, free0,
                      iota, qos_t):
    def step(carry, inputs):
        free, count = carry
        arrival, svc = inputs
        _, _, finish = _step(free, arrival, svc, type_of_slot, priority)
        return (free, count + ((finish - arrival) <= qos_t)
                .astype(jnp.int32)), None
    (free, count), _ = jax.lax.scan(step, (free0, jnp.int32(0)),
                                    (arrivals, service_T))
    return count, free


def _stream_unchanged(free, count, shift, arrivals, batches, valid, lut_T,
                      type_of_slot, priority, iota, qos_t):
    free = free - shift

    def step(carry, inputs):
        free, count = carry
        arrival, batch, ok = inputs
        _, _, finish = _step(free, arrival, lut_T[batch], type_of_slot,
                             priority)
        return (free, count + (ok & ((finish - arrival) <= qos_t))
                .astype(jnp.int32)), None
    (free, count), _ = jax.lax.scan(step, (free, count),
                                    (arrivals, batches, valid))
    return free, count


def plant(monkeypatch, fault: str) -> None:
    from repro.serving import simulator
    from repro.serving.simulator import (PoolSimulator, StreamingSimulator,
                                         StreamResult)

    if fault == "state_unchanged":
        monkeypatch.setattr(simulator, "_simulate_scan",
                            jax.jit(_scan_unchanged))
        inner = jax.vmap(_counts_unchanged,
                         in_axes=(None, None, 0, None, 0, None, None))
        monkeypatch.setattr(simulator, "_grid_counts_jit", jax.jit(
            jax.vmap(inner, in_axes=(0, None, None, None, None, None,
                                     None))))
        monkeypatch.setattr(simulator, "_stream_chunk_jit",
                            jax.jit(_stream_unchanged))
    elif fault == "half_mean":
        lat_single = PoolSimulator._lat_single
        qos = PoolSimulator.qos

        def half_single(self, config, policy):
            lat = lat_single(self, config, policy)
            return lat[: len(lat) // 2]

        def half_lanes(self, configs, **kw):
            if kw.get("workloads") is None:
                return qos(self, configs, **kw)
            cfg = np.asarray(configs)
            half = qos(self, cfg[: max(len(cfg) // 2, 1)], **kw)
            rates = np.resize(half.rates, (half.rates.shape[0], len(cfg)))
            return type(half)(rates=rates, state=None)

        monkeypatch.setattr(PoolSimulator, "_lat_single", half_single)
        monkeypatch.setattr(PoolSimulator, "qos", half_lanes)
    elif fault == "answer_altered":
        qos = PoolSimulator.qos
        stream_qos = StreamingSimulator.qos

        def altered(self, configs, **kw):
            r = qos(self, configs, **kw)
            return type(r)(rates=np.maximum(np.asarray(r.rates) - 0.01, 0)
                           if np.ndim(r.rates) else max(r.rates - 0.01, 0.0),
                           state=r.state, telemetry=r.telemetry)

        def altered_stream(self, config, n_queries, *, probe=None):
            r = stream_qos(self, config, n_queries, probe=probe)
            return StreamResult(rate=max(r.rate - 0.01, 0.0),
                                n_queries=r.n_queries, rebases=r.rebases)

        monkeypatch.setattr(PoolSimulator, "qos", altered)
        monkeypatch.setattr(StreamingSimulator, "qos", altered_stream)
    elif fault == "exchange_left_out":
        dispatch = PoolSimulator._dispatch_grid_sharded

        def first_device_only(self, arr, *a, **kw):
            counts = dispatch(self, arr, *a, **kw)
            # One load level per device: only the first device's comes back.
            return np.broadcast_to(counts[:1], counts.shape).copy()

        monkeypatch.setattr(PoolSimulator, "_dispatch_grid_sharded",
                            first_device_only)
    else:
        raise ValueError(fault)


def run_broken(cell_name: str, fault: str, monkeypatch) -> dict:
    plant(monkeypatch, fault)
    cell = run.Cell.load(ROOT, cell_name)
    return run.run_cell(cell, 20260917, 0.0, False, require_chip=False)


# The stream cell scores one pool: it has no batch of lanes to leave half
# of, and the QoS rate of half its 2^20 queries is that of the whole to
# within float32 rounding, so ``half_mean`` is no fault it can have.
CELL_FAULTS = [(cell, fault)
               for cell in ("mtwnd-search", "candle-sweep", "mtwnd-stream")
               for fault in ("state_unchanged", "half_mean", "answer_altered")
               if (cell, fault) != ("mtwnd-stream", "half_mean")]


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_fault_is_not_correct(monkeypatch, cell, fault):
    result = run_broken(cell, fault, monkeypatch)
    assert result["correct"] is False, result["check"]


def test_sound_run_is_correct():
    cell = run.Cell.load(ROOT, "candle-sweep")
    result = run.run_cell(cell, 20260917, 0.0, False, require_chip=False)
    assert result["correct"] is True, result["check"]


_FOUR_DEVICES = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
from bench.tests import test_faults
import pytest
mp = pytest.MonkeyPatch()
{plant}
from bench import run
cell = run.Cell.load(run.ROOT, "candle-sweep")   # on four devices
print(json.dumps(run.run_cell(cell, 20260917, 0.0, False,
                              require_chip=False)["correct"]))
"""


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_four_device_exchange(fault):
    plant_line = ("" if fault is None
                  else f"test_faults.plant(mp, {fault!r})")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES.format(root=str(ROOT),
                                                    plant=plant_line)],
        cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (fault is None)
