"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and every metric
(``bench/metrics/<metric>.py``) are found by name.  A run:

1. checks that JAX sees TPUs, as many as the cell asks for, and exits
   non-zero with no result otherwise;
2. sets up: the persistent compilation cache at ``.jax_cache/`` in the
   checkout, and one unit of the cell's traffic on a set-up seed, which
   compiles (or loads) every program the window runs;
3. measures: units of work back to back, each with a seed drawn from
   ``--seed``, until the unit in progress at ``--seconds`` completes.
   With ``--trace 1`` the first ``TRACE_SECONDS`` of the window run under
   the JAX profiler, and the metrics are the per-layer ones;
4. checks the answers of a seeded sample of the window's units against the
   plain reference (``reference.py``), once the window has closed;
5. prints each number compared beside its limit on standard error, and as
   the last line of standard output one JSON object with the metrics.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import trace_reduce  # noqa: E402
from bench.workloads import (Deployment, Spans, load_kind,  # noqa: E402
                             load_module)

# Backend compilations (or persistent-cache loads) of an executable.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# Longest traced stretch of a window.  A traced run takes 45 to 95 s more
# than a plain one on one v5e (the profiler's start, the export and the
# reading of 2 s of trace), against a run's limit of 360 s.
TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One ``workloads`` entry with what it names, loaded from files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        spec = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in spec["configs"]}
        config = load_json(root / configs[w["config"]]["file"])
        traffic = load_json(root / "bench" / "traffic"
                            / f"{w['traffic']}.json")
        e2e = [m for m in spec["end_to_end"]
               if name in m.get("workloads", [name])]
        names = {m["name"] for m in e2e}
        layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
        return cls(name, int(w["chips"]), config, traffic, e2e, layer)


@dataclass
class Context:
    """What a metric's reader reads."""

    cell: Cell
    setup_s: float
    elapsed_s: float
    work: dict
    spans: object
    trace: dict | None = None


def read_metrics(metrics: list[dict], ctx: Context, metrics_dir: Path):
    """{name: {"value", "unit"}} of every metric whose reader finds
    something to read; a reader that finds nothing returns None."""
    out = {}
    for m in metrics:
        value = load_module(metrics_dir / f"{m['name']}.py",
                            "bench_metric_").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(jax, chips: int, require_chip: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) != chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devices)} {dev.platform} device(s)")
    if require_chip and dev.device_kind not in load_json(BENCH
                                                         / "peaks.json"):
        raise NoChip(f"{dev.device_kind!r} is not in bench/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(jax) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class Tracer:
    """The JAX profiler over the first ``limit`` seconds of a window, ended
    at the first span that closes after the limit.  The ``trace.window``
    annotation marks the traced stretch.  It opens at the first span that
    closes after the profiler starts: the profiler's own start-up leaves
    the device idle for up to 0.1 s on a v5e, in no span of the program's."""

    def __init__(self, jax, limit: float):
        self.jax, self.limit = jax, limit
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.ann, self.t0, self.on = None, None, False

    def start(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        # Module executions only, which is all the reduction reads: the
        # scans' while loops put one event per op per step into a full
        # device trace, 0.7 to 1.8 million a second on a v5e.
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def tick(self) -> None:
        if not self.on:
            return
        if self.ann is None:
            self.ann = self.jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self.ann.__enter__()
            self.t0 = time.perf_counter()
        elif time.perf_counter() - self.t0 >= self.limit:
            self.stop()

    def stop(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        if self.on:
            self.jax.profiler.stop_trace()
            self.on = False

    def reduce(self, span_names, layers_dir: Path) -> dict:
        """The reduction of the recorded trace; the files go."""
        try:
            events = trace_reduce.extract(trace_reduce.find_xplane(self.dir),
                                          set(span_names)
                                          | {trace_reduce.WINDOW})
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce(events,
                                   trace_reduce.load_layers(layers_dir))


def unit_seeds(seed: int, stream: int):
    """Seeds of units drawn from the run's ``--seed``: stream 0 for the
    window's units, 1 for the check's sample, 2 for set-up."""
    rng = np.random.default_rng([seed, stream])
    while True:
        yield int(rng.integers(0, 2**31 - 1))


@dataclass
class Window:
    """What one measured window did."""

    units: list
    elapsed_s: float
    compiles: int
    trace: dict | None


class Bench:
    """One cell set up on this machine's devices; then any number of
    windows, each followed by the comparison of its answers with the
    plain reference.  ``run_cell`` makes one of each; ``control.py``
    reads many seeds' windows on one set-up."""

    def __init__(self, cell: Cell, *, root: Path = ROOT,
                 require_chip: bool = True, traced: bool = False):
        cache_dir = root / ".jax_cache"
        cache_dir.mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        if str(root / "src") not in sys.path:
            sys.path.insert(0, str(root / "src"))
        import jax

        self.jax, self.cell, self.root = jax, cell, root
        self.device = device_info(jax, cell.chips, require_chip)
        from repro.launch.compile_cache import enable_compile_cache

        self.t_jax = time.perf_counter() - T0
        enable_compile_cache()
        self.compiles = {"n": 0, "s": 0.0, "hits": 0}

        def on_duration(event, duration, **_):
            if event == _COMPILE_EVENT:
                self.compiles["n"] += 1
                self.compiles["s"] += duration

        def on_event(event, **_):
            if event == _CACHE_HIT_EVENT:
                self.compiles["hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        self.spans = Spans(traced=traced)
        self.tracer = Tracer(jax, TRACE_SECONDS) if traced else None
        kind = load_kind(root / "bench" / "kinds", cell.traffic["kind"])
        self.gen = kind(Deployment(cell.config), cell.traffic, self.spans)

    def warm(self, seed: int) -> None:
        """One unit on a set-up seed: compiles (or loads) every program
        the window runs."""
        self.gen.warm(next(unit_seeds(seed, 2)))
        self.spans.reset()

    def window(self, seed: int, seconds: float) -> Window:
        """Units back to back until the one in progress at ``seconds``
        completes; traced over its first ``TRACE_SECONDS`` if set up so
        (the first window only)."""
        tracer = self.tracer
        seeds = unit_seeds(seed, 0)
        c0 = self.compiles["n"]
        units = []
        if tracer:
            self.spans.on_tick = tracer.tick
            tracer.start()
        t0 = time.perf_counter()
        while not units or time.perf_counter() - t0 < seconds:
            units.append(self.gen.unit(next(seeds)))
        elapsed = time.perf_counter() - t0
        reduced = None
        if tracer:
            tracer.stop()
            self.spans.on_tick, self.tracer = None, None
            reduced = tracer.reduce((n for n, _, _ in self.spans.events),
                                    self.root / "bench" / "layers")
        return Window(units, elapsed, self.compiles["n"] - c0, reduced)

    def check(self, window: Window, seed: int, control=None) -> dict:
        """The window's answers against the plain reference, on a sample
        drawn from the seed: {number: {"value", "limit"}}."""
        readings = self.gen.check(window.units,
                                  np.random.default_rng([seed, 1]), control)
        readings["compiles_in_window"] = window.compiles
        limits = dict(self.cell.traffic["check"]["limits"],
                      compiles_in_window=0)
        return {k: {"value": v, "limit": limits.get(k)}
                for k, v in readings.items()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_chip: bool = True,
             control=None) -> dict:
    """One run of ``cell``: set-up, window, check.  Returns the result
    object (``control``: a reference precision whose answers stand in
    for the program's in the check; see ``control.py``)."""
    bench = Bench(cell, root=root, require_chip=require_chip, traced=trace)
    bench.warm(seed)
    setup_s = time.perf_counter() - T0
    c = bench.compiles
    print(f"set-up {setup_s:.3f} s: {bench.t_jax:.3f} s to JAX's devices, "
          f"{c['n']} compilations or cache loads "
          f"({c['s']:.3f} s, {c['hits']} cache hits)", file=sys.stderr)

    w = bench.window(seed, seconds)
    device = dict(bench.device, memory_peak_bytes=memory_peak(bench.jax))
    work = bench.gen.work()
    if w.trace is not None:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    ctx = Context(cell, setup_s, w.elapsed_s, work, bench.spans, w.trace)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           ctx, root / "bench" / "metrics")

    # Once the window has closed and the memory peak is read.
    check = bench.check(w, seed, control)
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in check.values())
    result = {"correct": correct, "attempted": work["units"],
              "failed": work["failed"], "metrics": metrics, "device": device}
    if w.trace is not None:
        result["breakdown"] = {"device_ops": w.trace["device_ops"],
                               "idle_gaps": w.trace["idle_gaps"]}
    result["check"] = check
    return result


def _finite(x):
    """The result line is strict JSON: a non-finite reading prints as the
    largest float (and has already failed its limit)."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not np.isfinite(x):
        return float(np.finfo(np.float64).max) * (-1 if x < 0 else 1)
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program to measure: {ROOT / 'src' / 'repro'} is missing",
              file=sys.stderr)
        return 2
    cell = Cell.load(ROOT, args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
