"""Chip smoke test: RIBBON's main paths on one TPU, checked, in one process.

    python chip_smoke.py               # phases a-f on one chip
    python chip_smoke.py --four-chips  # only the sharded grid sweep, 4 chips

Phases, each through the entry point a user calls:

  a. device check: the first device must be a TPU (no CPU fallback);
  b. RIBBON's BO search on the queueing simulator (mtwnd, the verify anchor),
     with a host-CPU replay that locates where the chip's search first
     departs from the CPU's, if it does;
  c. the wide lanes: ``PoolEvaluator.batch`` (B=32) and ``.grid`` (W=4 x
     B=32), one row and one cell checked bit for bit against the single-
     config ``qos`` lane;
  d. ``StreamingSimulator`` over 2^20 queries of a scaled spec, its prefix
     compared with ``PoolSimulator`` on ``spec.realize``;
  e. the ``spot-churn`` episode through ``ScenarioEngine`` on the simulator
     plane;
  f. live serving: ``repro.launch.serve.serve`` with full-width MT-WND cells,
     plus a check of the model's outputs against a float64 host reference.

Each phase prints one line: its wall seconds, the host set-up seconds and
the compile seconds inside them, and its result.  A phase that raises or
whose check fails ends the run with a non-zero exit code.  The last line of
a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MODEL = "mtwnd"
ANCHOR = ((4, 0, 1), 2.253)          # CPU result of the verify anchor search
# XLA compile (or persistent-cache load) of each executable.  Tracing and
# lowering are left out: they nest across jits and the cache skips neither.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile = {"s": 0.0, "n": 0, "hits": 0}


def _on_duration(event, duration, **_):
    if event == _COMPILE_EVENT:
        _compile["s"] += duration
        _compile["n"] += 1


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _compile["hits"] += 1


class PhaseFailed(AssertionError):
    pass


def _check(cond, what):
    if not cond:
        raise PhaseFailed(what)


def run_phase(tag: str, name: str, fn, **kwargs) -> dict:
    """Run one phase and print its line.  ``fn`` returns (set-up seconds,
    result dict); the compile seconds come from JAX's monitoring events."""
    c0 = dict(_compile)
    t0 = time.perf_counter()
    setup_s, result = fn(**kwargs)
    line = {"phase": name, "wall_s": time.perf_counter() - t0,
            "setup_s": setup_s, "compile_s": _compile["s"] - c0["s"],
            "compiles": _compile["n"] - c0["n"],
            "cache_hits": _compile["hits"] - c0["hits"], "result": result}
    print(f"[{tag}] {json.dumps(line)}", flush=True)
    return result


# --------------------------------------------------------------------- b
def _trace_divergence(a, b) -> dict | None:
    """First sample at which two search traces differ, and how."""
    for i, (x, y) in enumerate(zip(a.real, b.real)):
        if x.config != y.config:
            return {"index": i, "kind": "config", "chip": x.config,
                    "cpu": y.config}
        if x.qos_rate != y.qos_rate:
            return {"index": i, "kind": "rate", "config": x.config,
                    "chip": x.qos_rate, "cpu": y.qos_rate}
    if len(a.real) != len(b.real):
        return {"index": min(len(a.real), len(b.real)), "kind": "length"}
    return None


def _workload_ops_vs_cpu(cpu) -> dict:
    """Which step of the arrival draw rounds differently on the chip: the
    exponential transform of the uniform bits (a log), or the prefix sum
    of the gaps (fed the same gaps on both backends)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.pool import paper_spec

    spec = paper_spec(MODEL)
    key = jax.random.fold_in(spec._keys()[0], 0)

    def both(fn):
        chip = np.asarray(fn())
        with jax.default_device(cpu):
            return chip, np.asarray(fn())

    chip_e, cpu_e = both(lambda: jax.random.exponential(
        key, (spec.chunk,), dtype=jnp.float32))
    chip_c, cpu_c = both(lambda: jnp.cumsum(jnp.asarray(cpu_e)))
    return {"exponential_draws_differ": int(np.sum(chip_e != cpu_e)),
            "cumsum_of_same_gaps_differs": int(np.sum(chip_c != cpu_c)),
            "of": spec.chunk}


def phase_search(budget: int = 80):
    import jax
    import numpy as np

    from repro.core import run_ribbon
    from repro.core.gp import gp_posterior
    from repro.serving import PoolEvaluator, make_paper_setup

    t0 = time.perf_counter()
    ev, space, profile = make_paper_setup(MODEL)
    setup_s = time.perf_counter() - t0
    trace = run_ribbon(space, ev, budget=budget, start=(5, 0, 0))
    best = trace.best_feasible()
    _check(best is not None, "search found no feasible pool")
    _check(best.feasible and ev(best.config) >= 0.99,
           "best pool does not meet the QoS target")
    anchor_match = (best.config, best.cost) == ANCHOR

    # Where would the chip's search first depart from the CPU's?  The host
    # CPU backend replays each stage on the chip's own inputs; nothing of
    # the search above ran there.
    cpu = jax.devices("cpu")[0]
    wl = ev.workload
    with jax.default_device(cpu):
        ref_wl = make_paper_setup(MODEL)[0].workload
        ref_trace = run_ribbon(space, PoolEvaluator(profile, ev.types, wl),
                               budget=budget, start=(5, 0, 0))
    differ = np.flatnonzero(ref_wl.arrivals != wl.arrivals)
    batches_differ = int(np.sum(ref_wl.batches != wl.batches))
    divergence = _trace_divergence(trace, ref_trace)

    x = np.asarray([e.config for e in trace.real], np.float32)
    y = np.asarray([e.qos_rate for e in trace.real], np.float32)
    q = space.enumerate().astype(np.float32)
    denom = np.maximum(np.asarray(space.bounds, np.float32), 1.0)
    args = (x, y, np.ones(len(x), np.float32), q, denom)
    mean_chip, _ = gp_posterior(*args)
    with jax.default_device(cpu):
        mean_cpu, _ = gp_posterior(*args)
    gp_diff = float(np.max(np.abs(np.asarray(mean_chip) -
                                  np.asarray(mean_cpu))))

    if differ.size or batches_differ:
        first = "workload"
    elif divergence is None:
        first = "none"
    else:
        first = "simulator" if divergence["kind"] == "rate" else "gp"
    return setup_s, {
        "best": best.config, "cost": best.cost, "qos_rate": best.qos_rate,
        "samples": trace.n_samples, "anchor": ANCHOR[0],
        "anchor_match": anchor_match,
        "workload_vs_cpu": {
            "arrivals_differ": int(differ.size),
            "first_at": int(differ[0]) if differ.size else None,
            "max_abs_diff_s": float(np.max(np.abs(ref_wl.arrivals
                                                  - wl.arrivals))),
            "batches_differ": batches_differ,
            **_workload_ops_vs_cpu(cpu)},
        "trace_vs_cpu_replay": divergence,
        "cpu_replay_best": ref_trace.best_feasible().config,
        "gp_mean_max_abs_diff_vs_cpu": gp_diff,
        "first_difference": first}


# --------------------------------------------------------------------- c
def _informative(rates) -> int:
    """Index of the rate nearest the QoS target: a cell whose count sits
    at the edge says more than a saturated 0 or 1."""
    import numpy as np
    return int(np.argmin(np.abs(np.asarray(rates) - 0.9)))


def phase_wide_lanes(b: int = 32, factors=(0.8, 1.0, 1.25, 1.5)):
    import numpy as np

    from repro.serving import PoolSimulator, make_paper_setup

    t0 = time.perf_counter()
    ev_b, space, profile = make_paper_setup(MODEL)
    ev_g = make_paper_setup(MODEL)[0]
    lattice = space.enumerate()
    rng = np.random.default_rng(0)
    configs = lattice[rng.choice(len(lattice), size=b, replace=False)]
    setup_s = time.perf_counter() - t0

    rates = ev_b.batch(configs)
    grid = ev_g.grid(configs, factors)
    _check(rates.shape == (b,) and grid.shape == (len(factors), b),
           "lane shapes")
    _check(np.all((grid >= 0) & (grid <= 1)), "grid rates out of [0, 1]")
    i = _informative(rates)
    single = ev_b.sim.qos(configs[i]).rates
    w = 2
    j = _informative(grid[w])
    ref = PoolSimulator(profile, ev_g.types,
                        ev_g.workload.scaled(factors[w])).qos(
        configs[j]).rates
    row_ok = single == rates[i]
    cell_ok = ref == grid[w, j]
    _check(row_ok, f"batch row {i}: {rates[i]!r} != single {single!r}")
    _check(cell_ok, f"grid cell [{w}, {j}]: {grid[w, j]!r} != {ref!r}")
    return setup_s, {
        "batch_B": b, "grid_WxB": [len(factors), b],
        "batch_row": {"index": i, "config": configs[i].tolist(),
                      "rate": float(rates[i]), "bit_identical": bool(row_ok)},
        "grid_cell": {"index": [w, j], "factor": factors[w],
                      "config": configs[j].tolist(),
                      "rate": float(grid[w, j]),
                      "bit_identical": bool(cell_ok)},
        "grid_mean_rate_by_factor": grid.mean(axis=1).tolist()}


# --------------------------------------------------------------------- d
def phase_stream(n_stream: int = 1 << 20, n_prefix: int = 1 << 16,
                 scale: float = 1.25, config=(5, 1, 1)):
    import jax
    import numpy as np

    from repro.serving import (AWS_INSTANCES, MODEL_PROFILES, PAPER_POOLS,
                               PoolSimulator, StreamingSimulator)
    from repro.serving.pool import paper_spec

    t0 = time.perf_counter()
    profile = MODEL_PROFILES[MODEL]
    types = [AWS_INSTANCES[n] for n in PAPER_POOLS[MODEL]["diverse"]]
    spec = paper_spec(MODEL).scaled(scale)
    stream = StreamingSimulator(profile, types, spec)
    wl = spec.realize(n_prefix)
    setup_s = time.perf_counter() - t0

    full = stream.qos(config, n_stream)
    _check(full.n_queries == n_stream and 0.0 <= full.rate <= 1.0,
           "streamed episode")
    prefix = stream.qos(config, n_prefix)
    mono = PoolSimulator(profile, types, wl).qos(config).rates
    count_stream = round(prefix.rate * n_prefix)
    count_mono = round(mono * n_prefix)

    # The arrivals themselves: device-scaled chunks (float64 emulated on the
    # chip) against the host's float64 division in realize.
    dev, base = [], 0.0
    for c in range(-(-n_prefix // spec.chunk)):
        arr, local, _ = spec.generate_chunk(c, base)
        dev.append(np.asarray(jax.device_get(arr)))
        base = float(np.asarray(local)[-1])
    dev = np.concatenate(dev)[:n_prefix]
    host = wl.arrivals.astype(np.float32)
    return setup_s, {
        "spec_scale": scale, "config": list(config),
        "streamed_queries": full.n_queries, "streamed_rate": full.rate,
        "rebases": full.rebases, "prefix": n_prefix,
        "prefix_count_stream": count_stream,
        "prefix_count_realize": count_mono,
        "counts_agree": count_stream == count_mono,
        "prefix_arrivals_differ": int(np.sum(dev != host))}


# --------------------------------------------------------------------- e
def phase_episode(n: int = 500):
    from repro.scenario import (ScenarioEngine, build_episode,
                                paper_simulator_plane)

    t0 = time.perf_counter()
    spec = build_episode("spot-churn", n=n)
    plane, space = paper_simulator_plane(MODEL, spec)
    setup_s = time.perf_counter() - t0
    report = ScenarioEngine(spec, plane, space).run()
    _check([e.kind for e in report.events]
           == ["spot_preemption", "price_change"], "episode events")
    n_total = sum(ph.n_queries for ph in spec.phases)
    _check(report.windows[0].start == 0 and report.windows[-1].end == n_total
           and all(a.end == b.start for a, b in zip(report.windows,
                                                     report.windows[1:])),
           "windows tile the episode")
    _check(0.0 <= report.qos_rate <= 1.0, "episode QoS rate")
    return setup_s, {
        "qos_rate": report.qos_rate,
        "violation_windows": sum(1 for w in report.windows
                                 if w.qos_rate < spec.qos_target),
        "n_windows": len(report.windows),
        "events": [{"kind": e.kind, "at_query": e.at_query,
                    "recovery_queries": e.recovery_queries}
                   for e in report.events],
        "recovered_all_events": report.recovered_all_events,
        "final_config": report.final_config, "bo_evals": report.bo_evals}


# --------------------------------------------------------------------- f
def _mtwnd_reference(params, batch):
    """Float64 host forward of MT-WND from the exact gathered rows."""
    import jax
    import numpy as np

    def mlp(layers, x, last_act=False):
        for k, layer in enumerate(layers):
            x = x @ np.asarray(layer["w"], np.float64) + np.asarray(
                layer["b"], np.float64)
            if k < len(layers) - 1 or last_act:
                x = np.maximum(x, 0.0)
        return x

    cat = np.asarray(batch["cat"])
    feats = [np.asarray(batch["dense"], np.float64)]
    for i, table in enumerate(params["tables"]):
        rows = np.asarray(jax.device_get(table[cat[:, i]]), np.float64)
        feats.append(rows.sum(axis=1))
    x = np.concatenate(feats, axis=-1)
    deep = mlp(params["bottom"], x, last_act=True)
    logits = np.concatenate([mlp(t, deep) for t in params["towers"]], -1)
    return 1.0 / (1.0 + np.exp(-(logits + mlp(params["wide"], x))))


def phase_serve(preset: str = "full", budget: int = 12, n_queries: int = 60,
                interpret: bool = False):
    from functools import partial

    import jax
    import numpy as np

    from repro.launch.serve import serve
    from repro.models.paper_models import make_random_batch, mtwnd_apply

    opt, engine = serve(model=MODEL, preset=preset, budget=budget,
                        n_queries=n_queries, verbose=False)
    samples = [{"config": e.config, "qos_rate": e.qos_rate, "cost": e.cost}
               for e in opt.trace.real]
    best = opt.trace.best_feasible()
    _check(samples, "no pool was served")
    lat, _ = engine.served_arrays()
    _check(np.all(np.isfinite(lat)) and np.all(lat > 0),
           "measured latencies")

    params = engine._params[engine.cell_types[0].name]
    batch = make_random_batch(MODEL, preset, 32, seed=1)
    plain = np.asarray(jax.jit(mtwnd_apply)(params, batch))
    kern = np.asarray(jax.jit(partial(mtwnd_apply, use_kernel=True,
                                      interpret=interpret))(params, batch))
    ref = _mtwnd_reference(params, batch)
    kern_diff = float(np.max(np.abs(kern - plain)))
    ref_diff = float(np.max(np.abs(plain - ref)))
    _check(plain.shape == ref.shape and np.all(np.isfinite(plain)),
           "model output shape")
    _check(kern_diff <= 1e-5, f"Pallas embedding bag vs gather: {kern_diff}")
    _check(ref_diff <= 3e-2, f"model vs float64 reference: {ref_diff}")
    return engine.setup_s, {
        "preset": preset, "samples": samples,
        "chosen": None if best is None else best.config,
        "chosen_cost": None if best is None else best.cost,
        "median_service_ms": float(np.median(
            [r.latency - r.wait for r in engine.records]) * 1e3),
        "kernel_vs_gather_max_abs": kern_diff,
        "model_vs_f64_reference_max_abs": ref_diff}


# ------------------------------------------------------------ four chips
def phase_four_chips(b: int = 128, factors=(0.8, 1.0, 1.25, 1.5)):
    import jax
    import numpy as np

    from repro.serving import PoolSimulator, make_paper_setup
    from repro.serving.simulator import _lane_mesh

    t0 = time.perf_counter()
    ev, space, profile = make_paper_setup(MODEL)
    lattice = space.enumerate()
    configs = lattice[np.random.default_rng(0).choice(
        len(lattice), size=b, replace=False)]
    setup_s = time.perf_counter() - t0

    sim = ev.sim
    grid = sim.qos(configs, workloads=list(factors)).rates      # sharded
    n_dev = jax.local_device_count()
    mesh_devices = [d.id for d in _lane_mesh(n_dev).devices.flat]
    shard_devices = sorted({s.device.id for a in sim._grid_arrs.values()
                            for s in a.addressable_shards})
    batched = np.stack([
        PoolSimulator(profile, ev.types, ev.workload.scaled(f)).qos(
            configs).rates for f in factors])                    # one device
    equal = grid == batched
    _check(len(set(mesh_devices)) == n_dev == 4, "lane mesh devices")
    _check(shard_devices == sorted(mesh_devices), "lanes not on every chip")
    _check(bool(equal.all()), f"{int((~equal).sum())} cells differ")
    return setup_s, {
        "grid_WxB": [len(factors), b], "lane_mesh_devices": mesh_devices,
        "arrival_shard_devices": shard_devices,
        "cells_bit_identical": int(equal.sum()), "cells": int(equal.size),
        "mean_rate_by_factor": grid.mean(axis=1).tolist()}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map grid sweep on 4 chips "
                         "against the unsharded batched lane")
    args = ap.parse_args(argv)

    # Imported before JAX so that a copy of this file outside the repository
    # fails here, before it touches the device or prints a line.
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"[a] device check failed: JAX's first device is "
              f"{dev.platform!r}, not a TPU", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"[a] --four-chips needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"[a] {json.dumps({'phase': 'device_check', 'result': device})}",
          flush=True)

    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    if args.four_chips:
        run_phase("4", "sharded_grid_vs_batched", phase_four_chips)
    else:
        run_phase("b", "ribbon_search", phase_search)
        run_phase("c", "wide_lanes", phase_wide_lanes)
        run_phase("d", "streaming", phase_stream)
        run_phase("e", "spot_churn_episode", phase_episode)
        run_phase("f", "live_serving", phase_serve)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
