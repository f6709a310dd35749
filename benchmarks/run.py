"""Benchmark harness: one module per paper figure + beyond-paper studies.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9,fig10]

Writes machine-readable results to bench_out/*.json and prints tables.
"""

from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_ablation_objective, bench_batch_dist, bench_batch_eval,
               bench_cardinality, bench_convergence, bench_cost_savings,
               bench_exploration_cost, bench_load_change, bench_pool_example,
               bench_qos_relax, bench_qos_violations, bench_scenarios,
               bench_tpu_cells, bench_tradeoff)
from .common import write_bench_json

BENCHES = [
    ("fig3_tradeoff", bench_tradeoff),
    ("fig4_pool_example", bench_pool_example),
    ("fig8_cardinality", bench_cardinality),
    ("fig9_cost_savings", bench_cost_savings),
    ("fig10_convergence", bench_convergence),
    ("fig11_batch_dist", bench_batch_dist),
    ("fig13_exploration_cost", bench_exploration_cost),
    ("fig14_qos_violations", bench_qos_violations),
    ("fig15_qos_relax", bench_qos_relax),
    ("fig16_load_change", bench_load_change),
    ("ablation_objective", bench_ablation_objective),
    ("beyond_tpu_cells", bench_tpu_cells),
    ("perf_batch_eval", bench_batch_eval),
    ("beyond_scenarios", bench_scenarios),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    enable_compile_cache()

    failures, summary = [], []
    for name, mod in BENCHES:
        if only and not any(name.startswith(o) or o in name for o in only):
            continue
        t0 = time.time()
        print(f"\n##### {name} #####")
        try:
            mod.run(quick=args.quick)
            status = "ok"
            print(f"[{name}] done in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            status = "failed"
            failures.append(name)
        summary.append({"name": name, "status": status,
                        "wall_time_s": time.time() - t0})
    # Machine-readable run record (stable schema) so the perf trajectory of
    # every bench is trackable across PRs, not just printed tables.
    write_bench_json("run_summary",
                     {"quick": bool(args.quick), "benches": summary})
    if failures:
        print(f"\nFAILED benches: {failures}")
        raise SystemExit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
