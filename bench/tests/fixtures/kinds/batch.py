"""Test fixture, a kind of request added as a new file: a fixed list of
pools scored through ``PoolEvaluator.batch`` on a fresh evaluator and
stream."""

from __future__ import annotations

import numpy as np

from bench.workloads import Deployment, Spans, arrival_gap


class Batch:
    def __init__(self, dep: Deployment, traffic: dict, spans: Spans):
        self.dep, self.t, self.spans = dep, traffic, spans
        self.pools = [tuple(int(c) for c in p) for p in traffic["pools"]]

    def unit(self, seed: int) -> dict:
        with self.spans("batch"):
            ev = self.dep.evaluator(seed)
            rates = ev.batch(self.pools)
        self.spans.counters["units"] += 1
        return {"seed": seed, "arrivals": ev.workload.arrivals,
                "rates": np.asarray(rates)}

    warm = unit

    def check(self, units, rng, control=None) -> dict:
        rec = units[int(rng.integers(len(units)))]
        dep, n = self.dep, self.dep.n
        arr, svc = dep.ref_stream(rec["seed"], n)
        gaps = [abs(round(r * n) - dep.ref_count(arr, svc, p))
                for p, r in zip(self.pools, rec["rates"])]
        return {"mean_gap": float(np.mean(gaps)),
                "arrival_gap_s": arrival_gap(rec["arrivals"], arr)}

    def work(self) -> dict:
        return {"units": self.spans.counters["units"], "failed": 0,
                "steps_per_dispatch": self.dep.n}


Kind = Batch
