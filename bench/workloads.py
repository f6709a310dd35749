"""What every kind of request shares, and the loader of kinds.

A traffic mix is a data file under ``bench/traffic/`` whose ``kind`` names
a file ``bench/kinds/<kind>.py``; that file's ``Kind`` class is built once
per run from the cell's configuration (a ``Deployment``), its traffic
file's parameters and the run's ``Spans``, and then serves units of work:

* ``warm(seed)`` runs what a unit runs, so that every program the window
  drives is compiled (or loaded) in set-up;
* ``unit(seed)`` makes one request of the system under test, with all the
  work behind it (drawing the query stream on the device included), and
  returns a record of what the program answered;
* ``work()`` counts what the units did (units, candidate-queries scored,
  search samples) and the scan steps of one dispatch;
* ``check(records, rng, control)`` compares a seeded sample of the answers
  with the plain reference (``reference.py``) and returns each number
  compared.  With ``control`` set, the answers of the reference computed in
  that lower precision stand in for the program's.

A new kind of request is a new file; a new cell of an existing kind is a
traffic file (data) only.  A kind whose semantics the plain reference
does not cover (another stream law, a routing policy) brings its own
reference beside it.

The numbers compared:

* ``mean_gap``: the mean, over the sampled lanes (pool x load level, each
  scored over the unit's stream), of the gap in queries between the
  lane's QoS count and the reference's;
* ``count_gap``: the gap in queries between a streamed pool's QoS count
  and the reference's;
* ``arrival_gap_s``: the widest gap between an arrival the simulator
  scans and the reference's.

A float32 program and a float64 reference part at near-ties: an instance
that frees within a microsecond of an arrival is idle to one and busy to
the other, and the queue then runs differently for a while.  Pools that
share a queue history part together, so on a 1500-query stream a few in a
hundred lanes move, now and then one by a hundred queries or more: a
lane's widest gap, or the share of lanes off, swings from seed to seed.
The mean gap over a few hundred lanes stays under a query or two; a fault
moves most lanes, and with them the mean.
"""

from __future__ import annotations

import importlib.util
from collections import Counter
from contextlib import contextmanager, nullcontext
from pathlib import Path
from time import perf_counter

import numpy as np

from . import reference as ref


class Spans:
    """Host spans of the benchmark's calls into the program, and counters.

    Each span is (name, start, end) on ``perf_counter``; while a profiler
    trace runs, each also goes into the trace as a ``TraceAnnotation`` so
    that the reduction can tell what the host was doing in a device gap.
    ``on_tick``, if set, is called as each span closes and after each
    streamed chunk: the harness ends a bounded trace there.
    """

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.events: list[tuple[str, float, float]] = []
        self.counters: Counter = Counter()
        self.on_tick = None

    def tick(self) -> None:
        if self.on_tick is not None:
            self.on_tick()

    def reset(self) -> None:
        self.events.clear()
        self.counters.clear()

    def _annotation(self, name: str):
        if not self.traced:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextmanager
    def __call__(self, name: str):
        t0 = perf_counter()
        with self._annotation(name):
            yield
        self.events.append((name, t0, perf_counter()))
        self.tick()

    def total(self, *names: str) -> tuple[float, int]:
        """(seconds, count) of the spans with these names."""
        d = [e - s for n, s, e in self.events if n in names]
        return float(sum(d)), len(d)


class Deployment:
    """The program's objects for one configuration file."""

    def __init__(self, config: dict):
        from repro.core import SearchSpace
        from repro.serving import InstanceType
        from repro.serving.instance import ModelProfile

        self.config = config
        m = dict(config["model"])
        self.profile = ModelProfile(
            name=m["name"], flops_per_sample=m["flops_per_sample"],
            act_bytes_per_sample=m["act_bytes_per_sample"],
            weight_bytes=m["weight_bytes"], qos_latency=m["qos_latency"],
            max_batch=m["max_batch"], median_batch=m["median_batch"],
            efficiency=dict(m["efficiency"]))
        self.types = [InstanceType(t, **config["instances"][t])
                      for t in config["pool"]]
        self.space = SearchSpace(bounds=tuple(config["bounds"]),
                                 prices=tuple(t.price for t in self.types))
        self.n = int(config["queries_per_eval"])
        self.max_instances = int(config["max_instances"])

    def spec(self, seed: int, scale: float = 1.0):
        from repro.serving import WorkloadSpec

        spec = WorkloadSpec(seed=int(seed), **self.config["stream"])
        return spec if scale == 1.0 else spec.scaled(scale)

    def evaluator(self, seed: int):
        from repro.serving import PoolEvaluator

        return PoolEvaluator(self.profile, self.types,
                             self.spec(seed).realize(self.n),
                             max_instances=self.max_instances)

    # -- the plain reference of this deployment -------------------------
    def ref_stream(self, seed: int, n: int, scale: float = 1.0,
                   prec=ref.F64):
        arr, bat = ref.stream(seed, n, self.config["stream"], scale, prec)
        return arr, ref.service_times(self.config, bat, prec)

    @staticmethod
    def ref_scaled(arrivals, factor: float, prec=ref.F64):
        """Arrivals under ``factor`` times heavier load, in ``prec``."""
        a = np.asarray(arrivals, prec.dtype) / np.asarray(factor, prec.dtype)
        return a.astype(np.float64)

    def ref_count(self, arrivals, service, counts, prec=ref.F64) -> int:
        return ref.fcfs_count(arrivals, service, counts,
                              self.config["model"]["qos_latency"], prec)


def arrival_gap(program_arrivals, ref_arrivals) -> float:
    """Widest gap, in seconds, between the arrivals the simulator scans
    (float32 on the device) and the reference's."""
    prog = np.asarray(program_arrivals, np.float32).astype(np.float64)
    return float(np.max(np.abs(prog - ref_arrivals)))


def load_module(path: Path, prefix: str):
    """Import one file of the benchmark (a kind, a metric) by its path."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kinds_dir: Path, kind: str):
    """The ``Kind`` class of ``kinds_dir/<kind>.py``."""
    path = kinds_dir / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"no kind of request {kind!r}: {path} is missing")
    return load_module(path, "bench_kind_").Kind
