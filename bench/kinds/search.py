"""Kind ``search``: provisioning requests, each RIBBON's search at its
budget over a fresh evaluator, ending in a committed pool."""

from __future__ import annotations

import numpy as np

from bench.workloads import Deployment, Spans, arrival_gap


class Search:
    """A provisioning request: RIBBON's search at its budget over a fresh
    evaluator, ending in a committed pool.

    Its work depends on the stream (the search stops early once EI
    stalls), so a unit is one round of requests over a fixed set of
    ``streams`` stream seeds, in an order drawn from the run's seed: every
    seed then measures the same work.  ``batch_q`` pools are asked for at
    a time (constant-liar batches past 1, scored in one dispatch through
    ``PoolEvaluator.batch``), as ``run_ribbon`` does."""

    def __init__(self, dep: Deployment, traffic: dict, spans: Spans):
        self.dep, self.t, self.spans = dep, traffic, spans
        base = int(traffic["stream_seed_base"])
        self.stream_seeds = [base + i for i in range(int(traffic["streams"]))]

    def decide(self, seed: int) -> dict:
        from repro.core import RibbonOptimizer

        sp, t = self.spans, self.t
        with sp("decision"):
            with sp("stream.realize"):
                ev = self.dep.evaluator(seed)
            opt = RibbonOptimizer(self.dep.space, qos_target=float(
                self.dep.config["qos_target"]), theta=float(t["theta"]),
                start=tuple(t["start"]))
            n, budget, q = 0, int(t["budget"]), int(t["batch_q"])
            while n < budget and not opt.done:
                with sp("ask"):
                    configs = opt.ask_batch(min(q, budget - n))
                if not configs:
                    break
                with sp("oracle"):
                    rates = (ev.batch(configs) if len(configs) > 1
                             else [ev(configs[0])])
                    rates = [float(r) for r in rates]
                with sp("tell"):
                    for config, rate in zip(configs, rates):
                        opt.tell(config, rate)
                        n += 1
                        if opt.done:
                            break
            best = opt.trace.best_feasible()
        sp.counters["decisions"] += 1
        sp.counters["samples"] += n
        sp.counters["failed"] += best is None
        return {"seed": seed, "arrivals": ev.workload.arrivals,
                "evaluated": [(e.config, e.qos_rate)
                              for e in opt.trace.real],
                "committed": None if best is None else best.config}

    def unit(self, seed: int) -> list[dict]:
        order = np.random.default_rng(seed).permutation(self.stream_seeds)
        return [self.decide(int(s)) for s in order]

    def warm(self, seed: int) -> None:
        self.decide(seed)

    def check(self, units, rng, control=None) -> dict:
        records = [r for u in units for r in u]
        k = min(int(self.t["check"]["units"]), len(records))
        pick = rng.choice(len(records), size=k, replace=False)
        dep, n = self.dep, self.dep.n
        gaps, widest = [], 0.0
        for i in sorted(pick):
            rec = records[int(i)]
            arr, svc = dep.ref_stream(rec["seed"], n)
            # Every pool the search scored, the committed one among them.
            lanes = dict(rec["evaluated"])
            if control is None:
                got = {c: round(r * n) for c, r in lanes.items()}
                got_arr = rec["arrivals"]
            else:
                c_arr, c_svc = dep.ref_stream(rec["seed"], n, prec=control)
                got = {c: dep.ref_count(c_arr, c_svc, c, control)
                       for c in lanes}
                got_arr = c_arr
            gaps += [abs(got[c] - dep.ref_count(arr, svc, c)) for c in lanes]
            widest = max(widest, arrival_gap(got_arr, arr))
        return {"mean_gap": float(np.mean(gaps)),
                "arrival_gap_s": widest}

    def work(self) -> dict:
        c = self.spans.counters
        return {"units": c["decisions"], "failed": c["failed"],
                "samples": c["samples"], "steps_per_dispatch": self.dep.n}


Kind = Search
