"""Kind ``stream``: verification of a pool over a long stream drawn on the
device chunk by chunk (``StreamingSimulator``)."""

from __future__ import annotations

from time import perf_counter

from bench.workloads import Deployment, Spans


class Stream:
    """Verification of a committed pool over a long stream drawn on the
    device chunk by chunk (``StreamingSimulator``)."""

    def __init__(self, dep: Deployment, traffic: dict, spans: Spans):
        self.dep, self.t, self.spans = dep, traffic, spans
        self.pool = tuple(int(c) for c in traffic["pool"])
        self.scale = float(traffic["load_scale"])
        self.n = int(traffic["n_queries"])

    def unit(self, seed: int, n: int | None = None) -> dict:
        from repro.serving import StreamingSimulator

        n = self.n if n is None else n
        sp = self.spans
        with sp("stream"):
            sim = StreamingSimulator(self.dep.profile, self.dep.types,
                                     self.dep.spec(seed, self.scale),
                                     max_instances=self.dep.max_instances)
            # A span per chunk: from one chunk's dispatch to the next's.
            state = {"t": perf_counter(), "ann": None}

            def probe(c):
                sp.events.append(("stream.chunk", state["t"], perf_counter()))
                if state["ann"] is not None:
                    state["ann"].__exit__(None, None, None)
                    state["ann"] = None
                sp.tick()
                if sp.traced:
                    import jax

                    state["ann"] = jax.profiler.TraceAnnotation(
                        "stream.chunk")
                    state["ann"].__enter__()
                state["t"] = perf_counter()

            try:
                res = sim.qos(self.pool, n, probe=probe)
            finally:
                if state["ann"] is not None:
                    state["ann"].__exit__(None, None, None)
        sp.counters["units"] += 1
        sp.counters["candidate_queries"] += res.n_queries
        return {"seed": seed, "count": round(res.rate * res.n_queries)}

    def warm(self, seed: int) -> None:
        """Two chunks compile (or load) every program a unit runs."""
        self.unit(seed, 2 * self.dep.config["stream"]["chunk"])

    def check(self, units, rng, control=None) -> dict:
        k = min(int(self.t["check"]["units"]), len(units))
        dep, gap = self.dep, 0.0
        for i in sorted(rng.choice(len(units), size=k, replace=False)):
            rec = units[int(i)]
            arr, svc = dep.ref_stream(rec["seed"], self.n, self.scale)
            want = dep.ref_count(arr, svc, self.pool)
            if control is None:
                got = rec["count"]
            else:
                c_arr, c_svc = dep.ref_stream(rec["seed"], self.n,
                                              self.scale, control)
                got = dep.ref_count(c_arr, c_svc, self.pool, control)
            gap = max(gap, abs(got - want))
        return {"count_gap": gap}

    def work(self) -> dict:
        c = self.spans.counters
        return {"units": c["units"], "failed": 0,
                "candidate_queries": c["candidate_queries"],
                "steps_per_dispatch": self.dep.config["stream"]["chunk"]}


Kind = Stream
