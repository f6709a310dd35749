"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

In one process (set-up once), for each seed: the window and the check of
``bench/run.py``'s ``Bench``, with that seed (a short ``--seconds`` will
do): the same units, the same sample, the same comparison.  For
the program's seeds this gives the lower readings.  For the control's
seeds the reference computed in bfloat16 (the precision below the float32
the configurations state) stands in for the program's answers, on the
same units and the same sample, and gives the upper readings.  One JSON
line per reading; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402
from bench.run import Bench, Cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = Bench(Cell.load(ROOT, args.workload))
    bench.warm(0)
    prec = reference.BF16
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in dict.fromkeys(seeds + control_seeds):
        window = bench.window(seed, args.seconds)
        runs = []
        if seed in seeds:
            runs.append(("program", None))
        if seed in control_seeds:
            runs.append((prec.name, prec))
        for who, control in runs:
            t1 = time.perf_counter()
            check = bench.check(window, seed, control)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "answers": who, "units": len(window.units),
                              "readings": {k: c["value"]
                                           for k, c in check.items()},
                              "check_s": time.perf_counter() - t1,
                              "device": bench.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
