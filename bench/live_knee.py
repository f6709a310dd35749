"""Sweep of a live verify cell's arrival rate: the highest rate at which
its pool keeps QoS at the target, on this machine's chip.

    python bench/live_knee.py --workload dsv2lite-live --queries 80 \
        --rates 1,2,4,6,8,12,16

The live plane's service times do not depend on the arrival rate (its
clock is virtual), so one process serves ``--queries`` queries of a
stream of the cell's law once, on one cell, and replays the pool's FCFS
queue (``reference.fcfs_count``) over those measured times, each divided
by its cell type's speed, with the arrivals compressed to each rate.  One
JSON line per rate, then the knee: the highest rate of the sweep whose
QoS rate is at least the configuration's ``qos_target``.  The cell's
traffic sets its rate at 0.8 x that knee.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import reference  # noqa: E402
from bench.run import Bench, Cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--queries", type=int, default=80)
    ap.add_argument("--stream-seed", type=int, default=3000)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    bench = Bench(cell)
    t0 = time.perf_counter()
    bench.warm(0)
    print(json.dumps({"set_up_s": time.perf_counter() - t0,
                      "compiles": bench.compiles}), flush=True)
    kind = bench.gen
    kind.t = dict(kind.t, queries=args.queries)
    wl = kind._workload(args.stream_seed)
    eng = kind.engine
    eng.configure((1,) + (0,) * (len(eng.cell_types) - 1))
    eng.serve(wl, qos_latency=kind.qos)
    # wall seconds of each query (cell1's speed is the first cell's)
    wall = np.asarray([r.latency - r.wait for r in eng.records]) \
        * eng.cell_types[0].speed
    speeds = np.asarray([ct.speed for ct in eng.cell_types])
    svc = wall[None, :] / speeds[:, None]
    pool = tuple(kind.t["pool"])
    base_rate = wl.rate_qps
    target = float(cell.config["qos_target"])
    knee = None
    print(json.dumps({"queries": len(wall), "wall_s_median": float(
        np.median(wall)), "wall_s_p90": float(np.percentile(wall, 90)),
        "wall_s_sum": float(wall.sum())}), flush=True)
    for rate in sorted(float(r) for r in args.rates.split(",")):
        arrivals = wl.arrivals * (base_rate / rate)
        ok = reference.fcfs_count(arrivals, svc, pool, kind.qos)
        qos = ok / len(wall)
        print(json.dumps({"rate_qps": rate, "qos": qos}), flush=True)
        if qos >= target:
            knee = rate
    print(json.dumps({"knee_qps": knee, "rate_at_0.8": None if knee is None
                      else 0.8 * knee, "pool": pool, "target": target}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
