"""Kind ``live``: the live serving plane (``ClusterEngine.serve``), where
each query runs for real on the chip and its measured service time feeds
the same virtual-clock FCFS queue the simulator models.

Two modes, named by the traffic file's ``mode``:

* ``search``: provisioning requests, each RIBBON's search at its budget
  over the live plane (``launch/serve.py``'s loop): every sample serves a
  fixed stream live on the pool it proposes, ending in a committed pool.
  A unit is a round of requests over the fixed ``streams``, in an order
  drawn from the run's seed.  ``work()`` counts decisions as units.
* ``verify``: a committed ``pool`` serves streams live.  A unit is a
  round of verifications over the fixed ``streams``, in an order drawn
  from the run's seed, so every window does the same work.  ``work()``
  counts the queries served and scored (``candidate_queries``).

The model is a paper model (the traffic's ``model`` at ``preset``) or the
configuration's architecture (a file with the model's ``config.json``
keys, built into the program's ``ArchConfig``).  Its weights are the plain
reference's, made from the set-up seed with the shapes and counts the
benchmark states (``bench/ref_dsv2lite.py`` from the configuration's
keys, ``bench/ref_mtwnd.py`` from the traffic's ``model_dims``) and
handed to the program (``ClusterEngine.load_params``), so the reference
reads nothing of the program's own structure.  Every executable is
compiled in set-up.

The numbers compared, over ``check.streams`` served streams drawn from the
check's seed, each with ``check.keep`` of its queries' outputs kept (drawn
from the unit's seed):

* ``count_gap``: the widest gap in queries between the QoS count the
  engine returned for a stream and the plain reference's FCFS count
  (``reference.fcfs_count``) over the service times it recorded;
* ``logit_err`` (an architecture): for every real request of every kept
  query, the prefill's last-position logits and the decode logits at the
  decode loop's kept steps (``KEPT_STEPS`` spread over 1..T) against the
  reference's forward over the prompt plus the program's own generated
  tokens: the largest |difference| over the reference logits' standard
  deviation;
* ``out_err`` (a paper model): the largest |difference| between the kept
  batches' outputs and the float64 reference.

With ``control`` set, the reference one precision below the
configuration's stands in for the program's answers: the FCFS count in
that precision, the architecture's forward with weights and latent cache
in float8_e4m3fn (its precision is bfloat16), a paper model's forward in
bfloat16 (its precision is float32).
"""

from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.workloads import Deployment, Spans


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file holding the
    model's ``config.json`` keys (the held experts and vocabulary as
    ``reduced`` states them, the router's width from ``published``)."""
    from repro.configs.base import ArchConfig

    rs = config.get("rope_scaling") or {}
    nd, rd = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    return ArchConfig(
        name=config["name"], family="moe",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), d_head=nd + rd,
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]), attention="mla",
        q_lora_rank=int(config["q_lora_rank"] or 0),
        kv_lora_rank=int(config["kv_lora_rank"]), qk_rope_dim=rd,
        qk_nope_dim=nd, v_head_dim=int(config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(rs.get("factor", 0.0)),
        yarn_original_max_pos=int(rs.get(
            "original_max_position_embeddings", 0)),
        yarn_beta_fast=float(rs.get("beta_fast", 32)),
        yarn_beta_slow=float(rs.get("beta_slow", 1)),
        yarn_mscale=float(rs.get("mscale", 1.0)),
        yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        n_experts=int(config["published"]["n_routed_experts"]),
        experts_held=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_shared_experts=int(config["n_shared_experts"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        first_dense_layers=int(config["first_k_dense_replace"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]))


def program_params(w: dict, config: dict) -> dict:
    """The reference's weights (``bench/ref_dsv2lite.py``) in the program's
    parameter layout: the same arrays, renamed."""
    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": w["final_norm"], "layers": w["moe"]}
    if int(config["first_k_dense_replace"]):
        out["dense_layers"] = w["dense"]
    return out


class Live:
    def __init__(self, dep: Deployment, traffic: dict, spans: Spans):
        # The program's live plane has to serve architectures and keep a
        # sample of outputs; a program without it fails here, at once.
        from repro.serving.engine import ClusterEngine  # noqa: F401
        from repro.serving.llm import LLMExecutor  # noqa: F401

        self.dep, self.t, self.spans = dep, traffic, spans
        self.mode = traffic["mode"]
        if self.mode not in ("search", "verify"):
            raise SystemExit(f"live: unknown mode {self.mode!r}")
        base = int(traffic["stream_seed_base"])
        self.stream_seeds = [base + i for i in range(int(traffic["streams"]))]
        self.is_llm = "hidden_size" in dep.config
        self.qos = float(dep.config["model"]["qos_latency"])
        self.engine, self.workloads = None, {}

    # -- set-up ----------------------------------------------------------
    def _cells(self):
        from repro.serving.engine import CellType

        return [CellType(c["name"], price=float(c["price"]),
                         chips=int(c["chips"]), speed=float(c["speed"]),
                         preset=self.t.get("preset", "full"))
                for c in self.t["cells"]]

    def _workload(self, seed: int):
        from repro.serving.workload import (LengthLaw, LLMWorkloadSpec,
                                            WorkloadSpec)

        law = dict(self.dep.config["stream"], **self.t.get("stream", {}))
        spec = WorkloadSpec(seed=int(seed), **law)
        if self.is_llm:
            c = self.dep.config
            spec = LLMWorkloadSpec(spec, LengthLaw(**c["prompt"]),
                                   LengthLaw(**c["output"]))
        return spec.realize(int(self.t["queries"]))

    def _build(self, seed: int) -> None:
        """The engine, serving the plain reference's weights made from
        ``seed`` (converted into the program's layout); every executable
        compiled."""
        from repro.serving.engine import ClusterEngine

        cells = self._cells()
        c = self.dep.config
        if self.is_llm:
            from bench import ref_dsv2lite

            self.arch = arch_config(c)
            self.engine = ClusterEngine(
                self.arch, cells, seed=seed,
                prompt_bins=tuple(c["prompt_bins"]),
                max_output=int(c["output"]["hi"]),
                max_batch=int(c["stream"]["max_batch"]))
            self.weights = ref_dsv2lite.weights(c, seed)
            self.engine.load_params(program_params(self.weights, c))
        else:
            from bench import ref_mtwnd

            self.engine = ClusterEngine(self.t["model"], cells, seed=seed)
            self.weights = ref_mtwnd.weights(self.t["model_dims"], seed)
            self.engine.load_params(self.weights)
        self.engine.warmup(int(c["stream"]["max_batch"]))
        self.workloads = {s: self._workload(s) for s in self.stream_seeds}

    def warm(self, seed: int) -> None:
        if self.engine is None:
            self._build(seed)
        if self.mode == "search":
            self.decide(self.stream_seeds[0], np.random.default_rng(seed))
        else:
            self.verify(self.stream_seeds[0], np.random.default_rng(seed))

    # -- one stream served on one pool -----------------------------------
    def _serve(self, seed: int, pool, rng) -> dict:
        eng, wl = self.engine, self.workloads[seed]
        n = len(wl.arrivals)
        keep = rng.choice(n, size=min(int(self.t["check"]["keep"]), n),
                          replace=False)
        eng.configure(pool)
        rate = eng.serve(wl, qos_latency=self.qos, keep=keep)
        recs = eng.records
        self.spans.counters["queries"] += len(recs)
        return {"seed": seed, "pool": tuple(int(c) for c in pool),
                "rate": float(rate), "ok": int(round(rate * len(recs))),
                "arrivals": np.asarray([r.arrival for r in recs]),
                "service": np.asarray([r.latency - r.wait for r in recs]),
                "rows": np.asarray([r.batch_size for r in recs]),
                "prompt": np.asarray([r.prompt_len for r in recs]),
                "steps": np.asarray([r.output_len for r in recs]),
                "kept": dict(eng.kept)}

    def verify(self, seed: int, rng) -> dict:
        with self.spans("verify"):
            rec = self._serve(seed, self.t["pool"], rng)
        self.spans.counters["verifications"] += 1
        return rec

    def decide(self, seed: int, rng) -> dict:
        from repro.core import RibbonOptimizer, SearchSpace

        sp, t = self.spans, self.t
        space = SearchSpace(bounds=tuple(t["bounds"]),
                            prices=tuple(float(c["price"])
                                         for c in t["cells"]))
        samples = []
        with sp("decision"):
            opt = RibbonOptimizer(space, qos_target=float(
                self.dep.config["qos_target"]))
            for _ in range(int(t["budget"])):
                with sp("ask"):
                    config = opt.ask()
                if config is None or opt.done:
                    break
                with sp("oracle"):
                    rec = self._serve(seed, config, rng)
                with sp("tell"):
                    opt.tell(config, rec["rate"])
                samples.append(rec)
            best = opt.trace.best_feasible()
        sp.counters["decisions"] += 1
        sp.counters["samples"] += len(samples)
        sp.counters["failed"] += best is None
        return {"seed": seed, "samples": samples,
                "committed": None if best is None else best.config}

    def unit(self, seed: int):
        rng = np.random.default_rng(seed)
        order = rng.permutation(self.stream_seeds)
        if self.mode == "search":
            return [self.decide(int(s), rng) for s in order]
        return [self.verify(int(s), rng) for s in order]

    # -- the comparison with the plain reference -------------------------
    def _served(self, units) -> list[dict]:
        """Every served stream of the window, in order."""
        if self.mode == "search":
            return [s for u in units for d in u for s in d["samples"]]
        return [r for u in units for r in u]

    def check(self, units, rng, control=None) -> dict:
        served = self._served(units)
        k = min(int(self.t["check"]["streams"]), len(served))
        pick = sorted(int(i) for i in rng.choice(len(served), size=k,
                                                  replace=False))
        gap = 0
        for i in pick:
            rec = served[i]
            svc = np.broadcast_to(rec["service"], (len(rec["pool"]),
                                                   len(rec["service"])))
            want = ref.fcfs_count(rec["arrivals"], svc, rec["pool"],
                                  self.qos)
            got = (rec["ok"] if control is None else
                   ref.fcfs_count(rec["arrivals"], svc, rec["pool"],
                                  self.qos, control))
            gap = max(gap, abs(got - want))
        out = {"count_gap": gap}
        kept = [(rec, q, o) for rec in (served[i] for i in pick)
                for q, o in rec["kept"].items()]
        if self.is_llm:
            out["logit_err"] = self._logit_err(kept, control)
        else:
            out["out_err"] = self._out_err(kept, control)
        return out

    def _out_err(self, kept, control) -> float:
        from bench import ref_mtwnd

        worst = 0.0
        for _, _, (batch, out) in kept:
            want = ref_mtwnd.forward(self.weights, batch)
            got = (np.asarray(out, np.float64) if control is None
                   else ref_mtwnd.forward(self.weights, batch,
                                          ref_mtwnd.BF16))
            worst = max(worst, float(np.max(np.abs(got - want))))
        return worst

    def _logit_err(self, kept, control) -> float:
        """Every real row of every kept query: the prefill's last logits
        and the decode logits at each kept step."""
        import jax

        from bench import ref_dsv2lite

        c = self.dep.config
        # one padded length for every query: one reference program
        length = int(c["prompt_bins"][-1]) + int(c["output"]["hi"])
        worst = 0.0
        for rec, q, out in kept:
            prompt = int(rec["prompt"][q])
            steps = int(rec["steps"][q])
            tokens, fed, prefill, logits, at = jax.device_get(
                (out["tokens"], out["fed"], out["prefill"], out["kept"],
                 out["at"]))
            at = [int(a) for a in at]
            # the positions scored: the prompt's last, then each kept step
            pos = [prompt - 1] + [prompt - 1 + a for a in at]
            first = [0] + [1 + j for j, a in enumerate(at) if a not in at[:j]]
            for r in range(int(rec["rows"][q])):
                seq = np.concatenate([tokens[r, :prompt], fed[r, :steps]])
                want = ref_dsv2lite.logits_at(self.weights, seq, pos, c,
                                              length)
                if control is None:
                    got = np.concatenate([prefill[r, :1], logits[:, r]])
                else:
                    got = ref_dsv2lite.logits_at(self.weights, seq, pos, c,
                                                 length,
                                                 quant="float8_e4m3fn")
                for i in first:
                    g = np.asarray(got[i], np.float64)
                    worst = max(worst, float(np.max(np.abs(g - want[i]))
                                             / want[i].std()))
        return worst

    def work(self) -> dict:
        c = self.spans.counters
        units = c["decisions"] if self.mode == "search" else (
            c["verifications"] // len(self.stream_seeds))
        return {"units": units, "failed": c["failed"],
                "samples": c["samples"], "candidate_queries": c["queries"],
                "steps_per_dispatch": 1}


Kind = Live

