"""Serving driver: ``python -m repro.launch.serve --model mtwnd``.

The paper's full loop on the live execution plane: build heterogeneous
serving cells, let RIBBON's BO find the cheapest QoS-meeting cell mix against
real measured latencies, then hold the optimal pool and keep serving, with
the autoscaler watching for load changes and the failure path re-optimizing
after cell loss.

``--model`` names a paper model or an architecture of the registry
(``python -m repro.launch.serve --model deepseek-v2-lite``): an LLM's
queries then carry prompt and output lengths, and at ``full`` it serves
one chip's share of the deployment where the registry states one
(``CHIP_SHARES``), else the published config.
"""

from __future__ import annotations

import argparse
from dataclasses import replace

from ..configs import ARCHS, CHIP_SHARES
from ..core import RibbonOptimizer, SearchSpace
from ..models.paper_models import PAPER_MODELS
from ..serving.engine import DEFAULT_TPU_CELLS, ClusterEngine
from ..serving.workload import LengthLaw, LLMWorkloadSpec, WorkloadSpec
from .compile_cache import enable_compile_cache

# An LLM's prompt bins, longest output and length laws, by preset: the
# full ones are the live DeepSeek-V2-Lite cell's (chat requests with about
# 1k-token histories and short answers).
LLM_PRESETS = {
    "full": dict(bins=(512, 2048), max_output=256,
                 prompt=LengthLaw(1024, 1.0, 128, 2048),
                 output=LengthLaw(128, 0.6, 16, 256)),
    "smoke": dict(bins=(16, 32), max_output=8,
                  prompt=LengthLaw(20, 1.0, 4, 32),
                  output=LengthLaw(4, 0.6, 1, 8)),
}


def _engine(model: str, cells, preset: str, seed: int):
    """The live engine for a paper model or a registry architecture, and
    the LLM preset (None for a paper model)."""
    if model in PAPER_MODELS:
        return ClusterEngine(model, cells, seed=seed), None
    cfg = ARCHS[model]
    cfg = cfg.reduced() if preset == "smoke" else CHIP_SHARES.get(model, cfg)
    llm = LLM_PRESETS[preset]
    return ClusterEngine(cfg, cells, seed=seed, prompt_bins=llm["bins"],
                         max_output=llm["max_output"]), llm


def serve(model: str = "mtwnd", n_queries: int = 60, rate_qps: float = 40.0,
          qos_latency: float = 0.2, qos_target: float = 0.9,
          bounds=(4, 3, 2), budget: int = 12, seed: int = 0,
          preset: str = "full", verbose: bool = True):
    """``preset`` sizes the model every cell serves: ``"full"`` is the
    published width (paper_models.*_PRESETS; an architecture's one-chip
    share), ``"smoke"`` a reduced one."""
    cells = [replace(c, preset=preset) for c in DEFAULT_TPU_CELLS]
    engine, llm = _engine(model, cells, preset, seed)
    if verbose:
        print("[serve] warming up cell executables ...")
    engine.warmup()
    spec = WorkloadSpec(seed=seed, rate_qps=rate_qps, median_batch=8,
                        max_batch=32)
    if llm is not None:
        spec = LLMWorkloadSpec(spec, llm["prompt"], llm["output"])
    wl = spec.realize(n_queries)
    space = SearchSpace(bounds=bounds, prices=tuple(c.price for c in cells))

    def evaluate(config):
        engine.configure(config)
        return engine.serve(wl, qos_latency=qos_latency)

    opt = RibbonOptimizer(space, qos_target=qos_target)
    for i in range(budget):
        cfg = opt.ask()
        if cfg is None or opt.done:
            if cfg is None and opt.trace.best_feasible() is None and verbose:
                print("[serve] search space infeasible under this QoS target")
            break
        rate = evaluate(cfg)
        opt.tell(cfg, rate)
        if verbose:
            print(f"[serve] sample {i + 1}: config {cfg} rate {rate:.3f} "
                  f"price ${engine.pool_price(cfg):.2f}/h")
    best = opt.trace.best_feasible()
    if best is not None and verbose:
        print(f"[serve] optimal pool {best.config} at "
              f"${best.cost:.2f}/h (QoS rate {best.qos_rate:.3f})")
    return opt, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mtwnd",
                    choices=sorted(PAPER_MODELS) + sorted(ARCHS))
    ap.add_argument("--queries", type=int, default=60)
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--qos-ms", type=float, default=200.0)
    ap.add_argument("--budget", type=int, default=12)
    ap.add_argument("--preset", default="full", choices=["full", "smoke"])
    args = ap.parse_args()
    enable_compile_cache()
    serve(model=args.model, n_queries=args.queries, rate_qps=args.rate,
          qos_latency=args.qos_ms / 1e3, budget=args.budget,
          preset=args.preset)


if __name__ == "__main__":
    main()
