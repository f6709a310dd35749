"""Kind ``sweep``: capacity sweeps, every lattice point at every load
level through ``PoolEvaluator.grid``."""

from __future__ import annotations

import numpy as np

from bench.workloads import Deployment, Spans, arrival_gap


class Sweep:
    """A capacity sweep: every lattice point at every load level, scored
    through ``PoolEvaluator.grid`` on a fresh evaluator and stream."""

    def __init__(self, dep: Deployment, traffic: dict, spans: Spans):
        self.dep, self.t, self.spans = dep, traffic, spans
        self.lattice = dep.space.enumerate()
        self.factors = tuple(float(f) for f in traffic["load_factors"])

    def unit(self, seed: int) -> dict:
        sp = self.spans
        with sp("sweep"):
            with sp("stream.realize"):
                ev = self.dep.evaluator(seed)
            qos = ev.sim.qos

            def dispatch(*args, **kwargs):
                with sp("sweep.dispatch"):
                    return qos(*args, **kwargs)

            ev.sim.qos = dispatch
            grid = ev.grid(self.lattice, self.factors)
        sp.counters["units"] += 1
        sp.counters["candidate_queries"] += grid.size * self.dep.n
        return {"seed": seed, "arrivals": ev.workload.arrivals, "grid": grid}

    warm = unit

    def check(self, units, rng, control=None) -> dict:
        k = min(int(self.t["check"]["units"]), len(units))
        dep, n = self.dep, self.dep.n
        gaps, widest = [], 0.0
        for i in sorted(rng.choice(len(units), size=k, replace=False)):
            rec = units[int(i)]
            n_w, n_b = rec["grid"].shape
            lanes = int(self.t["check"]["lanes"])
            # Every load level is sampled alike: on several chips each
            # level's rows are a different chip's shard.
            ws = np.arange(lanes) % n_w
            bs = rng.choice(n_b, size=lanes, replace=False)
            arr, svc = dep.ref_stream(rec["seed"], n)
            if control is None:
                got_arr = rec["arrivals"]
            else:
                c_arr, c_svc = dep.ref_stream(rec["seed"], n, prec=control)
                got_arr = c_arr
            widest = max(widest, arrival_gap(got_arr, arr))
            for w, b in zip(ws, bs):
                f, cfg = self.factors[w], self.lattice[b]
                want = dep.ref_count(dep.ref_scaled(arr, f), svc, cfg)
                if control is None:
                    got = round(rec["grid"][w, b] * n)
                else:
                    got = dep.ref_count(dep.ref_scaled(c_arr, f, control),
                                        c_svc, cfg, control)
                gaps.append(abs(got - want))
        return {"mean_gap": float(np.mean(gaps)),
                "arrival_gap_s": widest}

    def work(self) -> dict:
        c = self.spans.counters
        return {"units": c["units"], "failed": 0,
                "candidate_queries": c["candidate_queries"],
                "steps_per_dispatch": self.dep.n}


Kind = Sweep
