"""The ``live`` kind on tiny fixtures (CPU): both modes run through
``run.py`` and are found by name, the check catches a planted fault, and
the live metric readers read hand-made span records.

The fixtures are written to a copy of the benchmark, beside the cells'
own files: a tiny architecture with the model's ``config.json`` keys, a
smoke MT-WND search and a verification of the tiny LLM."""

import copy
import json
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from bench import live_spans, model_cost, run
from bench.run import ROOT
from repro import tracing

CELLS = [{"name": "cell1", "price": 1.2, "chips": 1, "speed": 1.0},
         {"name": "cell4", "price": 4.8, "chips": 4, "speed": 3.4},
         {"name": "cell8", "price": 9.6, "chips": 8, "speed": 6.0}]


def _tiny_arch() -> dict:
    """dsv2lite.json with every width cut to a CPU's size."""
    c = json.loads((ROOT / "bench" / "configs" / "dsv2lite.json").read_text())
    c.update(name="tinyllm", hidden_size=64, intermediate_size=128,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
             v_head_dim=16, num_attention_heads=4, num_key_value_heads=4,
             moe_intermediate_size=32, n_routed_experts=4, num_hidden_layers=3,
             num_experts_per_tok=3, n_shared_experts=1, vocab_size=256,
             published={"num_hidden_layers": 27, "n_routed_experts": 8,
                        "vocab_size": 512},
             prompt={"median": 20, "sigma": 1.0, "lo": 4, "hi": 32},
             output={"median": 4, "sigma": 0.6, "lo": 1, "hi": 8},
             prompt_bins=[16, 32])
    c["stream"] = dict(c["stream"], max_batch=4, median_batch=2)
    return c


TRAFFIC = {
    "tinyllm-verify": {
        "kind": "live", "mode": "verify", "pool": [1, 1, 0], "cells": CELLS,
        "stream": {"rate_qps": 20.0}, "queries": 4, "streams": 2,
        "stream_seed_base": 2000,
        "check": {"streams": 2, "keep": 4,
                  "limits": {"count_gap": 0, "logit_err": 0.6}}},
    "mtwnd-search-smoke": {
        "kind": "live", "mode": "search", "model": "mtwnd", "preset": "smoke",
        "model_dims": {"n_tables": 3, "vocab": 128, "emb": 16, "bag": 4,
                       "dense": 8, "bottom": [32, 16], "tasks": 2,
                       "tower": [16, 8]},
        "cells": CELLS, "bounds": [2, 2, 1], "budget": 3, "queries": 40,
        "streams": 2, "stream_seed_base": 1000,
        "check": {"streams": 3, "keep": 4,
                  "limits": {"count_gap": 0, "out_err": 1e-5}}},
}


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tinyllm.json").write_text(
        json.dumps(_tiny_arch()))
    spec["configs"].append({"name": "tinyllm", "source": "x",
                            "file": "bench/configs/tinyllm.json",
                            "reduced": [], "why": "x"})
    for name, t in TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    spec["workloads"] += [
        {"name": "tinyllm-live", "config": "tinyllm",
         "traffic": "tinyllm-verify", "chips": 1, "why": "x"},
        {"name": "mtwnd-live-smoke", "config": "mtwnd",
         "traffic": "mtwnd-search-smoke", "chips": 1, "why": "x"}]
    for m in spec["end_to_end"]:
        if m["name"] == "decision_s":
            m["workloads"].append("mtwnd-live-smoke")
        if m["name"] == "scored_queries_per_s":
            m["workloads"].append("tinyllm-live")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_the_cells_are_declared_and_load():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells["dsv2lite-live"]["traffic"] == "live-verify"
    assert cells["mtwnd-live"]["traffic"] == "live-search"
    for name in ("dsv2lite-live", "mtwnd-live"):
        cell = run.Cell.load(ROOT, name)
        assert cell.traffic["kind"] == "live"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    cfg = json.loads((ROOT / "bench" / "configs" / "dsv2lite.json")
                     .read_text())
    assert (cfg["hidden_size"], cfg["num_attention_heads"]) == (2048, 16)
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (512, 128, 64, 128)
    assert cfg["intermediate_size"] == 10944
    assert cfg["moe_intermediate_size"] == 1408
    assert cfg["published"]["n_routed_experts"] == 64
    assert cfg["num_experts_per_tok"] == 6 and cfg["n_shared_experts"] == 2
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]


@pytest.mark.parametrize("cell,e2e,err", [
    ("tinyllm-live", "scored_queries_per_s", "logit_err"),
    ("mtwnd-live-smoke", "decision_s", "out_err")])
def test_live_cells_run_and_are_correct(bench_copy, cell, e2e, err):
    c = run.Cell.load(bench_copy, cell)
    result = run.run_cell(c, 7, 0.0, False, root=bench_copy,
                          require_chip=False)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1
    assert e2e in result["metrics"]
    assert result["check"]["count_gap"]["value"] == 0
    assert result["check"][err]["value"] <= result["check"][err]["limit"]


def test_verify_units_repeat_the_same_work(bench_copy):
    bench = run.Bench(run.Cell.load(bench_copy, "tinyllm-live"),
                      root=bench_copy, require_chip=False)
    bench.warm(3)
    a = bench.gen.unit(11)
    b = bench.gen.unit(12)
    assert sorted(r["seed"] for r in a) == sorted(r["seed"] for r in b)
    assert bench.gen.work()["candidate_queries"] == 2 * 2 * 4


def _faulty_check(bench_copy, cell, plant):
    bench = run.Bench(run.Cell.load(bench_copy, cell), root=bench_copy,
                      require_chip=False)
    bench.warm(5)
    window = bench.window(5, 0.0)
    plant(window.units)
    return bench.check(window, 5)


def test_a_wrong_qos_count_is_caught(bench_copy):
    def plant(units):
        for r in units[0]:
            r["ok"] += 1

    check = _faulty_check(bench_copy, "tinyllm-live", plant)
    assert check["count_gap"]["value"] > check["count_gap"]["limit"]


def test_wrong_logits_are_caught(bench_copy):
    """The prefill's logits read one position late (those of decode step
    1): a cache off by one token."""
    def plant(units):
        for r in units[0]:
            for out in r["kept"].values():
                out["prefill"] = out["kept"][:1].transpose(1, 0, 2)

    check = _faulty_check(bench_copy, "tinyllm-live", plant)
    assert check["logit_err"]["value"] > check["logit_err"]["limit"]


def _drop_shared(p):
    p["layers"]["moe"].pop("shared")


def _drop_layer(p):
    import jax

    p["layers"] = jax.tree.map(lambda a: a[:-1], p["layers"])


def _fewer_experts(p):
    import jax

    moe = p["layers"]["moe"]
    moe["experts"] = jax.tree.map(lambda a: a[:, :-1], moe["experts"])


def _no_dense_mlp(p):
    import jax.numpy as jnp

    mlp = p["dense_layers"]["mlp"]
    mlp["w2"] = jnp.zeros_like(mlp["w2"])


@pytest.mark.parametrize("fault", [_drop_shared, _drop_layer,
                                   _fewer_experts, _no_dense_mlp])
def test_a_program_that_leaves_part_of_the_model_out_is_caught(
        bench_copy, monkeypatch, fault):
    """The program loads the weights it is handed, then serves without the
    shared experts, a MoE layer, one of its held experts or the leading
    dense layer's feed-forward: the reference, whose weights and
    structure come from the configuration, is not fooled."""
    import jax

    from repro.serving.engine import ClusterEngine

    load = ClusterEngine.load_params

    def faulty(self, params):
        load(self, params)
        served = jax.tree.map(lambda a: a, self.llm.params)  # new containers
        fault(served)
        self.llm.params = served
        for ct in self.cell_types:
            self._params[ct.name] = served

    monkeypatch.setattr(ClusterEngine, "load_params", faulty)
    result = run.run_cell(run.Cell.load(bench_copy, "tinyllm-live"), 7, 0.0,
                          False, root=bench_copy, require_chip=False)
    assert not result["correct"]
    check = result["check"]
    assert check["logit_err"]["value"] > check["logit_err"]["limit"]


def test_a_wrong_model_output_is_caught(bench_copy):
    def plant(units):
        for d in units[0]:
            for s in d["samples"]:
                s["kept"] = {q: (b, o + 1e-3) for q, (b, o)
                             in s["kept"].items()}

    check = _faulty_check(bench_copy, "mtwnd-live-smoke", plant)
    assert check["out_err"]["value"] > check["out_err"]["limit"]


def test_the_control_fails_the_limits(bench_copy):
    from bench import reference

    bench = run.Bench(run.Cell.load(bench_copy, "tinyllm-live"),
                      root=bench_copy, require_chip=False)
    bench.warm(5)
    window = bench.window(5, 0.0)
    low = bench.check(window, 5, reference.BF16)
    assert low["logit_err"]["value"] > low["logit_err"]["limit"]


# -- the readers on hand-made records -------------------------------------

def _rec(name, start_ms, end_ms, sid, parent=None, **args):
    return tracing.Record(name, int(start_ms * 1e6), int(end_ms * 1e6), sid,
                          parent, None, args)


@pytest.fixture
def records(monkeypatch):
    """Two LLM queries of one stream, the second inside the stretch."""
    recs = [
        _rec("live.serve", 0, 100, 1, batch=3),
        _rec("live.prefill", 1, 41, 2, 1, batch=4, prompt_bin=512, rows=3,
             prompt=300, held_tokens=900),
        _rec("live.decode", 41, 91, 3, 1, batch=4, prompt_bin=512, rows=3,
             prompt=300, steps=20, active=100),
        _rec("live.serve", 3000, 3200, 4, batch=5),
        _rec("live.prefill", 3002, 3082, 5, 4, batch=8, prompt_bin=2048,
             rows=5, prompt=1500, held_tokens=4000),
        _rec("live.decode", 3082, 3182, 6, 4, batch=8, prompt_bin=2048,
             rows=5, prompt=1500, steps=50, active=300),
    ]
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    monkeypatch.setattr(tracing, "dropped", lambda: 0)
    monkeypatch.setattr(model_cost, "peaks", lambda: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    return recs


def _ctx(trace=None, events=((0.5, 2.9),)):
    spans = SimpleNamespace(events=[("verify", s, e) for s, e in events])
    cfg = json.loads((ROOT / "bench" / "configs" / "dsv2lite.json")
                     .read_text())
    return SimpleNamespace(trace=trace, spans=spans,
                           cell=SimpleNamespace(config=cfg))


def test_span_readers(records):
    ctx = _ctx()
    assert live_spans.prefill_ms_per_query(ctx) == pytest.approx(60.0)
    assert live_spans.decode_ms_per_step(ctx) == pytest.approx(150 / 70)
    assert live_spans.host_ms_per_query(ctx) == pytest.approx(
        (300 - 120 - 150) / 2)
    assert live_spans.execute_ms_p95(ctx) is None


def test_device_shares_read_only_the_stretch(records):
    trace = {"layer_s": {"live.prefill": 0.1, "live.decode": 0.2}}
    # the stretch opens as the first benchmark span closes (2.9 s) and
    # ends at the first closing 2 s later (5.0 s): the second query only
    ctx = _ctx(trace, events=((0.5, 2.9), (3.0, 5.0)))
    cfg = ctx.cell.config
    flops = model_cost.prefill_flops(cfg, 5, 1500, 4000)
    assert live_spans.mfu_prefill(ctx) == pytest.approx(
        100 * flops / (0.1 * 197e12))
    moved = model_cost.decode_bytes(cfg, 5, 1500, 50, 300)
    assert live_spans.decode_roofline(ctx) == pytest.approx(
        100 * moved / (0.2 * 819e9))
    assert live_spans.mfu_prefill(_ctx(None)) is None
    empty = copy.deepcopy(trace)
    empty["layer_s"] = {}
    assert live_spans.decode_roofline(_ctx(empty, ((0.5, 2.9),))) is None


def test_nothing_to_read_without_records(monkeypatch):
    monkeypatch.setattr(tracing, "records", lambda: [])
    ctx = _ctx({"layer_s": {"live.prefill": 1.0}})
    for reader in (live_spans.prefill_ms_per_query,
                   live_spans.decode_ms_per_step,
                   live_spans.host_ms_per_query, live_spans.execute_ms_p95,
                   live_spans.mfu_prefill, live_spans.decode_roofline):
        assert reader(ctx) is None


def test_cost_counts_are_lower_bounds_of_the_padded_work():
    cfg = json.loads((ROOT / "bench" / "configs" / "dsv2lite.json")
                     .read_text())
    # a prefill of 8 x 2048 real tokens: 8-9 TFLOP at the published widths
    held = 8 * 2048 * 6 * 8 // 64 * 4
    assert 7e12 < model_cost.prefill_flops(cfg, 8, 2048, held) < 1e13
    # one decode step moves the 0.46 GB of weights outside the routed
    # experts and 8 requests' latent cache at 1025 tokens (0.05 GB)
    step = model_cost.decode_bytes(cfg, 8, 1024, 1, 0)
    assert 0.5e9 < step < 0.52e9
    assert model_cost.decode_bytes(cfg, 8, 1024, 1, 16) - step == \
        pytest.approx(16 * 2 * 3 * 2048 * 1408)
    assert np.isfinite(step)
