"""Grid dispatches issued ahead of each fetch (see
``sim.grid_queued_per_wait.py``), in cells that report
scored_queries_per_s.x4."""

from pathlib import Path

from bench.workloads import load_module

read = load_module(Path(__file__).with_name("sim.grid_queued_per_wait.py"),
                   "bench_metric_").read
