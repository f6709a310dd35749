"""Execution plane: live serving cells + FCFS dispatcher.

A `ServingCell` is the TPU adaptation of the paper's "instance": a compiled
(jit) executable for one model on one submesh slice, with a price per hour
(chips × $/chip-hour) and a measured latency history.  The `ClusterEngine`
owns a pool of cells (counts per cell type — exactly RIBBON's configuration
vector), dispatches queries FCFS in pool-type order, executes them for real,
and reports the measured QoS satisfaction rate — the live analogue of
`PoolSimulator`, pluggable into the same `RibbonOptimizer`.

On this CPU container every cell maps to the single local device and serves a
reduced model; on a pod the same class carves submeshes via `mesh_devices`.
The virtual-time bookkeeping (arrival → wait → measured service) mirrors the
simulator so QoS semantics are identical across planes.

The model is a paper model (``PAPER_MODELS``: one ``apply`` per batch, a
parameter copy per cell type) or an ``ArchConfig`` LLM (by registry name
or config): one parameter copy for every cell type, and per query a
prefill then greedy decoding through the cache (``serving/llm.py``); its
workload carries each query's prompt and output lengths.

A paper model's query is served the input batch of its (preset, power-of-
two bucket): ``make_random_batch``'s draw, which is the same on every call,
so the engine draws each one once (in ``warmup``, or on the bucket's first
query) and holds it.

Program spans (``repro.tracing``): ``live.serve`` covers one query from
its dispatch to its record, and for a paper model carries ``drawn`` (1 if
that query drew its batch, 0 if it was held); ``live.execute`` a paper
model's measured execution; an LLM query's are ``live.prefill`` and
``live.decode``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import numpy as np

from .. import tracing
from ..configs.base import ArchConfig
from ..models.paper_models import PAPER_MODELS, make_random_batch
from .llm import LLMExecutor, LLMQuery
from .workload import Workload


@dataclass
class CellType:
    """A serving-cell flavor: model executable config + price."""

    name: str
    price: float              # $/hour for the slice
    chips: int = 1
    preset: str = "smoke"
    # artificial per-cell slowdown factor: lets the demo create genuinely
    # heterogeneous cell speeds on one physical device (a 1-chip cell is ~Kx
    # slower than an 8-chip cell for batched inference)
    speed: float = 1.0


class ServingCell:
    def __init__(self, cell_type: CellType, model_name: str, params,
                 apply_fn):
        self.cell_type = cell_type
        self.model_name = model_name
        self._apply = apply_fn
        self._params = params
        self.busy_until = 0.0       # virtual-time availability
        self.n_served = 0
        self.failed = False

    def execute(self, batch) -> float:
        """Run the batch for real; returns measured service seconds scaled by
        the cell's speed factor."""
        if self.failed:
            raise RuntimeError(f"cell {self.cell_type.name} is failed")
        t0 = time.monotonic()
        out = self._apply(self._params, batch)
        jax.block_until_ready(out)
        wall = time.monotonic() - t0
        self.n_served += 1
        self.last_output = out
        return wall / self.cell_type.speed


def fit_params(own, given):
    """``given`` cast leaf by leaf to the dtypes of ``own``; the two trees
    must match in structure and in every shape."""
    if jax.tree.structure(own) != jax.tree.structure(given):
        raise ValueError(f"parameter tree {jax.tree.structure(given)} is "
                         f"not the model's {jax.tree.structure(own)}")

    def fit(path, o, g):
        if tuple(o.shape) != tuple(np.shape(g)):
            raise ValueError(f"{jax.tree_util.keystr(path)}: shape "
                             f"{tuple(np.shape(g))}, the model's {o.shape}")
        return jax.numpy.asarray(g, o.dtype)

    return jax.tree_util.tree_map_with_path(fit, own, given)


@dataclass
class QueryRecord:
    arrival: float
    batch_size: int
    latency: float
    cell: str
    wait: float = 0.0         # queue time before service started
    hedged: bool = False
    # Index (in the live-cell order) of the cell whose availability this
    # query advanced — the hedge winner when a hedge overtook the primary.
    # Lets continuous-clock callers rebuild per-cell busy times for any
    # served prefix (LivePlane segment commits).
    slot: int = -1
    # LLM queries: prompt tokens and tokens generated per request
    prompt_len: int = 0
    output_len: int = 0


class ClusterEngine:
    """Pool of live cells + FCFS dispatch, with failure injection and
    hedged-request straggler mitigation."""

    def __init__(self, model_name: str | ArchConfig,
                 cell_types: list[CellType], seed: int = 0,
                 hedge_threshold: float | None = None,
                 prompt_bins=(512, 2048), max_output: int = 256,
                 max_batch: int = 32):
        self.cell_types = list(cell_types)
        self.hedge_threshold = hedge_threshold
        t0 = time.monotonic()
        key = jax.random.PRNGKey(seed)
        self._params = {}
        self._apply = {}
        self.llm = None
        if isinstance(model_name, ArchConfig) or model_name not in PAPER_MODELS:
            if not isinstance(model_name, ArchConfig):
                from ..configs import get_arch
                model_name = get_arch(model_name)
            self.llm = LLMExecutor(model_name, seed, prompt_bins, max_output,
                                   max_batch)
            self.model_name = model_name.name
            for ct in cell_types:
                self._params[ct.name] = self.llm.params
                self._apply[ct.name] = self.llm.run
        else:
            self.model_name = model_name
            self.model = PAPER_MODELS[model_name]
            for ct in cell_types:
                self._params[ct.name] = self.model.init(key, ct.preset)
                self._apply[ct.name] = jax.jit(self.model.apply)
        jax.block_until_ready(self._params)
        # Wall seconds spent building cell parameters and in ``warmup``:
        # set-up that no measured service time includes.
        self.setup_s = time.monotonic() - t0
        self.cells: list[ServingCell] = []
        self.records: list[QueryRecord] = []
        # Outputs of the queries ``serve`` was asked to keep, by index.
        self.kept: dict[int, object] = {}
        # A paper model's input batch per (preset, bucket), drawn once.
        self._batches: dict[tuple[str, int], dict] = {}

    def load_params(self, params) -> None:
        """Serve every cell type with ``params``, a checkpoint in the
        model's own layout: each leaf must have the shape of the model's
        own and is cast to its serving dtype.  Raises ValueError on any
        other tree or shape.  Cell types of one preset share the copy."""
        for ct in self.cell_types:
            params = fit_params(self._params[ct.name], params)
            self._params[ct.name] = params
        if self.llm is not None:
            self.llm.params = params

    def warmup(self, max_batch: int = 32) -> None:
        """Pre-compile every (cell type × power-of-two bucket) executable so
        compile time never pollutes measured service latencies (an LLM's:
        every batch bucket × prompt bin, shared by the cell types)."""
        t0 = time.monotonic()
        if self.llm is not None:
            self.llm.warmup(max_batch)
            self.setup_s += time.monotonic() - t0
            return
        b = 1
        while b <= max_batch:
            for ct in self.cell_types:
                batch, _ = self._input(ct.preset, b)
                out = self._apply[ct.name](self._params[ct.name], batch)
                jax.block_until_ready(out)
            b *= 2
        self.setup_s += time.monotonic() - t0

    def _input(self, preset: str, bucket: int) -> tuple[dict, bool]:
        """The paper model's input batch of ``bucket`` rows in ``preset``'s
        shapes, and whether this call drew it (it is held from then on:
        cell types of one preset share it)."""
        batch = self._batches.get((preset, bucket))
        if batch is not None:
            return batch, False
        batch = make_random_batch(self.model_name, preset, bucket)
        self._batches[preset, bucket] = batch
        return batch, True

    # ------------------------------------------------------------- pool ops
    def configure(self, config) -> None:
        """config = counts per cell type (RIBBON's x vector)."""
        self.cells = []
        for ct, count in zip(self.cell_types, config):
            for _ in range(int(count)):
                self.cells.append(ServingCell(ct, self.model_name,
                                              self._params[ct.name],
                                              self._apply[ct.name]))

    def fail_cell(self, index: int) -> CellType:
        """Inject a cell failure (node loss).  Returns the lost type."""
        cell = self.cells[index]
        cell.failed = True
        return cell.cell_type

    def preempt(self, type_index: int, count: int = 1) -> int:
        """Spot-preemption hook: the market reclaims up to ``count`` live
        cells of one type (scenario engine event).  Mechanically a batch of
        cell failures — the capacity is gone until the pool is re-provisioned
        by `configure`.  Returns the number of cells actually preempted."""
        name = self.cell_types[type_index].name
        hit = 0
        for cell in self.cells:
            if hit >= count:
                break
            if not cell.failed and cell.cell_type.name == name:
                cell.failed = True
                hit += 1
        return hit

    def active_config(self) -> tuple[int, ...]:
        counts = {ct.name: 0 for ct in self.cell_types}
        for c in self.cells:
            if not c.failed:
                counts[c.cell_type.name] += 1
        return tuple(counts[ct.name] for ct in self.cell_types)

    # ------------------------------------------------------------- serving
    def serve(self, workload: Workload, qos_latency: float,
              time_scale: float = 1.0, initial_busy=None,
              keep=()) -> float:
        """Serve the stream; returns the QoS satisfaction rate.

        Arrivals advance a virtual clock; service times are *measured* on the
        real device (scaled by cell speed).  `time_scale` stretches arrival
        gaps so CPU-speed executions map onto the workload's regime.
        `initial_busy` warm-starts the pool: one busy-until time per live
        cell in the (scaled) arrival frame — the continuous-clock carry a
        `LivePlane` threads across scenario segments.  Omitted, every cell
        starts idle (the whole-stream accounting every cold path uses).
        The outputs of the queries whose indices are in ``keep`` stay in
        ``kept`` (with the batch a paper model served), for a check.
        """
        self.records = []
        self.kept = {}
        keep = frozenset(int(i) for i in keep)
        live = [c for c in self.cells if not c.failed]
        if not live:
            return 0.0
        if initial_busy is None:
            for c in live:
                c.busy_until = 0.0
        else:
            if len(initial_busy) != len(live):
                raise ValueError(
                    f"initial_busy has {len(initial_busy)} entries for "
                    f"{len(live)} live cells")
            for c, b in zip(live, initial_busy):
                c.busy_until = float(b)
        pos = {id(c): k for k, c in enumerate(live)}
        llm = self.llm is not None
        prompts = workload.prompt_lens if llm else None
        outputs = workload.output_lens if llm else None
        ok = 0
        for i, (arrival, bsz) in enumerate(zip(workload.arrivals * time_scale,
                                               workload.batches)):
            with tracing.span("live.serve", batch=int(bsz)) as sp:
                idle = [c for c in live if c.busy_until <= arrival]
                cell = idle[0] if idle else min(live,
                                                key=lambda c: c.busy_until)
                start = max(arrival, cell.busy_until)
                if llm:
                    batch = LLMQuery(int(bsz), int(prompts[i]),
                                     int(outputs[i]), i)
                else:
                    # bucket batch sizes to powers of two: bounds the
                    # number of compiled executables per cell (standard
                    # serving practice)
                    bucket = 1 << int(np.ceil(np.log2(max(int(bsz), 1))))
                    batch, drawn = self._input(cell.cell_type.preset, bucket)
                    if sp is not None:
                        sp.args["drawn"] = int(drawn)
                svc = self._execute(cell, batch, llm)
                if i in keep:
                    self.kept[i] = (cell.last_output if llm
                                    else (batch, cell.last_output))
                finish = start + svc
                wait = start - arrival
                hedged = False
                if (self.hedge_threshold is not None
                        and start - arrival > self.hedge_threshold):
                    # straggler mitigation: duplicate to the next-free cell
                    # and take the earlier finish
                    alt = min((c for c in live if c is not cell),
                              key=lambda c: c.busy_until, default=None)
                    if alt is not None:
                        alt_start = max(arrival, alt.busy_until)
                        alt_svc = self._execute(alt, batch, llm)
                        alt_finish = alt_start + alt_svc
                        if alt_finish < finish:
                            finish = alt_finish
                            alt.busy_until = alt_finish
                            wait = alt_start - arrival
                            hedged = True
                winner = cell
                if not hedged:
                    cell.busy_until = finish
                else:
                    winner = alt
                latency = finish - arrival
                self.records.append(QueryRecord(
                    float(arrival), int(bsz), float(latency),
                    cell.cell_type.name, wait=float(wait), hedged=hedged,
                    slot=pos[id(winner)],
                    prompt_len=int(prompts[i]) if llm else 0,
                    output_len=int(outputs[i]) if llm else 0))
                if latency <= qos_latency:
                    ok += 1
        return ok / len(workload.arrivals)

    @staticmethod
    def _execute(cell: ServingCell, batch, llm: bool) -> float:
        if llm:                  # live.prefill / live.decode inside
            return cell.execute(batch)
        with tracing.span("live.execute", batch=len(next(iter(
                batch.values())))):
            return cell.execute(batch)

    def served_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(latencies, waits) of the last `serve` call, in arrival order —
        the measured-plane feed for `LoadMonitor.observe` (the simulator's
        analogue is `PoolSimulator.simulate`'s `lat`/`waits`)."""
        lat = np.asarray([r.latency for r in self.records], dtype=np.float64)
        waits = np.asarray([r.wait for r in self.records], dtype=np.float64)
        return lat, waits

    def pool_price(self, config=None) -> float:
        if config is not None:
            return float(sum(ct.price * int(c)
                             for ct, c in zip(self.cell_types, config)))
        return float(sum(c.cell_type.price for c in self.cells
                         if not c.failed))


DEFAULT_TPU_CELLS = [
    CellType("cell1", price=1.2, chips=1, speed=1.0),
    CellType("cell4", price=4.8, chips=4, speed=3.4),
    CellType("cell8", price=9.6, chips=8, speed=6.0),
]
