"""DeepSeek-V2(-Lite) on the normal serving path, at a small size on the CPU.

Contracts under test:

* prefill (padded prompts of one length, padding rows) then greedy
  decoding through the latent cache (``launch.steps``) reproduces the
  plain reference's full forward over the prompt plus the program's own
  generated tokens (``models/reference_deepseek_v2.py``);
* expert shares: with E experts split into shares, every share's routed
  output summed, plus the shared experts once, is the uncut layer;
* YaRN's inverse frequencies and softmax scale against hand-computed
  values;
* MLA with a MoE layer: prefill then decode through the cache equals
  ``forward``, dropless, for the capacity-buffer layer and the expert
  share alike;
* the LLM workload's lengths come from streams of their own, leaving the
  arrivals and batches bit-identical;
* ``ClusterEngine`` serving the smoke LLM: its QoS count is an
  event-by-event FCFS replay of its own records, and it records the
  ``live.*`` spans under a profiler session.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import ARCHS
from repro.configs.deepseek_v2_lite import CONFIG
from repro.launch.steps import (KEPT_STEPS, make_decode_loop,
                                make_prefill_step)
from repro.models import reference_deepseek_v2 as ref
from repro.models.layers import (init_moe_params, mla_softmax_scale,
                                 moe_expert_share, rope_cos_sin, swiglu_mlp,
                                 yarn_inv_freq)
from repro.models.transformer import get_model
from repro.serving.engine import DEFAULT_TPU_CELLS, ClusterEngine, fit_params
from repro.serving.workload import LengthLaw, LLMWorkloadSpec, WorkloadSpec

# 8 experts, 4 held here, top-3 of them, one shared expert, a leading
# dense layer and two MoE layers.
SMALL = dataclasses.replace(CONFIG.reduced(), n_experts=8, experts_held=4,
                            top_k=3)


def _err(got, want):
    """Largest |difference| over the reference logits' standard deviation."""
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / want.std())


def test_small_config_keeps_the_published_shape():
    assert SMALL.attention == "mla" and SMALL.q_lora_rank == 0
    assert SMALL.first_dense_layers == 1 and SMALL.n_moe_layers == 2
    assert SMALL.n_shared_experts == 1 and not SMALL.norm_topk_prob
    assert SMALL.yarn_factor == 40.0 and SMALL.expert_share


@pytest.mark.parametrize("prompt,steps", [(11, 5), (16, 3)])
def test_prefill_then_decode_matches_the_reference(prompt, steps):
    """Float32 weights and activations on both sides: the program and the
    reference differ by summation order only (the absorbed latent decode,
    the grouped expert matmul), about 1e-6 of the logits' spread; 1e-4
    leaves that a hundredfold room, while a wrong position, a dropped
    expert or a missing YaRN scale moves logits by 0.1 or more."""
    api = get_model(SMALL)
    params = api.init_params(jax.random.PRNGKey(7), jnp.float32)
    bucket, pbin, rows, max_out = 4, 16, 3, 8
    tokens = jax.random.randint(jax.random.PRNGKey(8), (bucket, pbin), 0,
                                SMALL.vocab_size)
    lengths = jnp.where(jnp.arange(bucket) < rows, prompt, 0)
    prefill = make_prefill_step(api, max_len=pbin + max_out)
    cache, logits = prefill(params, {"tokens": tokens, "lengths": lengths})
    out = make_decode_loop(api, max_out)(params, cache, logits,
                                         jnp.int32(steps))
    at = [int(a) for a in out["at"]]
    assert at[0] == 1 and at[-1] == steps and at == sorted(at)
    for r in range(rows):
        seq = np.concatenate([np.asarray(tokens[r, :prompt]),
                              np.asarray(out["fed"][r, :steps])])
        want = np.asarray(ref.forward(params, seq, SMALL))
        assert _err(logits[r, 0], want[prompt - 1]) < 1e-4
        for j, step in enumerate(at):
            if step in at[:j]:      # a repeated step: kept in its first slot
                continue
            assert _err(out["kept"][j, r], want[prompt - 1 + step]) < 1e-4
    # the first token fed is the prefill's greedy pick
    np.testing.assert_array_equal(out["fed"][:rows, 0],
                                  np.argmax(logits[:rows, 0], axis=-1))
    assert int(out["active"]) > 0


def test_padding_rows_and_positions_reach_no_expert():
    api = get_model(SMALL)
    params = api.init_params(jax.random.PRNGKey(1), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                SMALL.vocab_size)
    prefill = make_prefill_step(api, max_len=24)
    cache, _ = prefill(params, {"tokens": tokens,
                                "lengths": jnp.array([5, 5, 0, 0])})
    # 2 rows x 5 tokens x top-3 pairs at most reach the 4 held experts
    counts = np.asarray(cache["held_tokens"])
    assert counts.shape == (SMALL.n_moe_layers, SMALL.experts_held)
    assert 0 < counts.sum(axis=1).max() <= 2 * 5 * 3
    assert int(cache["t"]) == 5
    assert np.all(np.asarray(cache["pos"])[5:] == -1)


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_expert_shares_sum_to_the_uncut_layer(shares):
    """Each chip of a deployment holds E/shares experts; what the shares
    compute, plus the shared expert counted once, is the whole layer."""
    cfg = dataclasses.replace(SMALL, experts_held=SMALL.n_experts)
    params = init_moe_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(params, x.reshape(14, -1), cfg).reshape(x.shape)
        held = cfg.n_experts // shares
        part = dataclasses.replace(cfg, experts_held=held)
        total = swiglu_mlp(params["shared"], x)
        for s in range(shares):
            p = {"router": params["router"],
                 "experts": {k: w[s * held:(s + 1) * held]
                             for k, w in params["experts"].items()}}
            out, counts = moe_expert_share(p, x, part, first_expert=s * held)
            total = total + out
            assert counts.shape == (held,)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_expert_share_is_dropless_under_skew():
    """Every token routed to one expert: none is dropped."""
    cfg = dataclasses.replace(SMALL, experts_held=SMALL.n_experts, top_k=1,
                              n_shared_experts=0)
    params = init_moe_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    params["router"] = params["router"].at[:, 2].add(100.0)
    # positive activations: expert 2's logit is the largest for every token
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                  (1, 32, cfg.d_model))) + 0.1
    out, counts = moe_expert_share(params, x, cfg)
    assert int(counts[2]) == 32
    with jax.default_matmul_precision("highest"):
        want = ref.moe(params, x[0], cfg)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_yarn_against_a_hand_computed_table():
    """dim 8, theta 1e4, factor 4 over 64 positions, beta 32/1: the
    correction range is [0, 2], the ramp (0, 0.5, 1, 1); the first
    frequency is kept, the second half kept, the last two divided by 4."""
    cfg = dataclasses.replace(CONFIG, qk_rope_dim=8, yarn_factor=4.0,
                              yarn_original_max_pos=64)
    table = [1.0, 0.5 * 0.1 + 0.5 * 0.025, 0.01 / 4, 0.001 / 4]
    np.testing.assert_allclose(yarn_inv_freq(8, 1e4, cfg), table, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(cfg), table, rtol=1e-6)
    # DeepSeek-V2-Lite: 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert mla_softmax_scale(CONFIG) == pytest.approx(192 ** -0.5 * m * m)
    # mscale == mscale_all_dim leaves cos/sin unscaled; otherwise their
    # ratio scales both
    pos = jnp.arange(3)
    c0, _ = rope_cos_sin(pos, 8, 1e4, cfg)
    c1, s1 = rope_cos_sin(pos, 8, 1e4, dataclasses.replace(
        cfg, yarn_mscale=1.0, yarn_mscale_all_dim=0.5))
    k = (0.1 * np.log(4.0) + 1) / (0.05 * np.log(4.0) + 1)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c0) * k, rtol=1e-6)
    assert float(s1[0, 0]) == 0.0


@pytest.mark.parametrize("layer", ["capacity", "expert_share"])
def test_mla_moe_decode_matches_forward(layer):
    """MLA with a MoE layer on the serving path (prefill, then several
    decode steps through the latent cache) against ``forward``; dropless
    (the smoke capacity factor is 8)."""
    base = ARCHS["minicpm3-4b"].reduced()
    cfg = dataclasses.replace(base, family="moe", n_experts=4, top_k=2,
                              d_expert=32)
    if layer == "expert_share":
        cfg = dataclasses.replace(cfg, experts_held=4, n_shared_experts=1)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(9), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, 14), 0,
                                cfg.vocab_size)
    full, _ = api.forward(params, tokens)
    cache, last = api.prefill(params, tokens[:, :10], max_len=16)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, 9]), rtol=2e-3, atol=2e-3)
    for i in range(10, 14):
        logits, cache = api.decode_step(params, cache, tokens[:, i:i + 1])
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, i]), rtol=2e-3,
                                   atol=2e-3)


def _llm_spec(seed=5, n=12):
    base = WorkloadSpec(seed=seed, rate_qps=40.0, median_batch=2, sigma=0.8,
                        max_batch=4)
    return base, LLMWorkloadSpec(base, LengthLaw(20, 1.0, 4, 32),
                                 LengthLaw(4, 0.6, 1, 8))


def test_llm_lengths_leave_the_arrival_stream_untouched():
    base, spec = _llm_spec()
    wl, plain = spec.realize(300), base.realize(300)
    np.testing.assert_array_equal(wl.arrivals, plain.arrivals)
    np.testing.assert_array_equal(wl.batches, plain.batches)
    assert wl.prompt_lens.min() >= 4 and wl.prompt_lens.max() <= 32
    assert wl.output_lens.min() >= 1 and wl.output_lens.max() <= 8
    assert 15 <= np.median(wl.prompt_lens) <= 25
    np.testing.assert_array_equal(spec.realize(300).prompt_lens,
                                  wl.prompt_lens)
    assert not np.array_equal(wl.prompt_lens[:100], wl.output_lens[:100])
    scaled = wl.scaled(2.0)
    np.testing.assert_array_equal(scaled.output_lens, wl.output_lens)


def _fcfs_replay(records, n_cells, qos):
    """Event by event: each query goes to the first idle cell in pool
    order at its arrival, else the one that frees first, and takes its
    recorded service time."""
    busy = [0.0] * n_cells
    ok = 0
    for r in records:
        idle = [k for k in range(n_cells) if busy[k] <= r.arrival]
        k = idle[0] if idle else min(range(n_cells), key=busy.__getitem__)
        start = max(r.arrival, busy[k])
        assert start - r.arrival == pytest.approx(r.wait, abs=1e-12)
        service = r.latency - r.wait
        busy[k] = start + service
        ok += busy[k] - r.arrival <= qos
    return ok


@pytest.fixture(scope="module")
def llm_engine():
    eng = ClusterEngine(SMALL, DEFAULT_TPU_CELLS, seed=3,
                        prompt_bins=(16, 32), max_output=8, max_batch=4)
    eng.warmup(4)
    return eng


def test_engine_serves_the_smoke_llm(llm_engine):
    _, spec = _llm_spec(seed=11)
    wl = spec.realize(16)
    llm_engine.configure((1, 1, 0))
    # a load under which queries queue: arrivals 10x denser
    qos = 0.05
    rate = llm_engine.serve(wl.scaled(10.0), qos_latency=qos, keep=(0, 5))
    recs = llm_engine.records
    assert [r.prompt_len for r in recs] == list(wl.prompt_lens)
    assert [r.output_len for r in recs] == list(wl.output_lens)
    assert any(r.wait > 0 for r in recs)
    assert rate * len(recs) == _fcfs_replay(recs, 2, qos)
    assert sorted(llm_engine.kept) == [0, 5]
    kept = llm_engine.kept[5]
    assert kept["tokens"].shape[1] == 16 if wl.prompt_lens[5] <= 16 else 32
    assert kept["kept"].shape == (KEPT_STEPS, kept["tokens"].shape[0],
                                  SMALL.vocab_size)


def test_engine_records_live_spans(llm_engine, tmp_path):
    _, spec = _llm_spec(seed=12)
    wl = spec.realize(3)
    llm_engine.configure((1, 0, 0))
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        llm_engine.serve(wl, qos_latency=1.0)
    recs = tracing.records()
    tracing.clear()
    serve = [r for r in recs if r.name == "live.serve"]
    pre = [r for r in recs if r.name == "live.prefill"]
    dec = [r for r in recs if r.name == "live.decode"]
    assert len(serve) == len(pre) == len(dec) == 3
    serve_ids = {r.sid for r in serve}
    assert all(r.parent in serve_ids for r in pre + dec)
    for r, n in zip(dec, wl.output_lens):
        assert r.args["steps"] == n and r.args["active"] > 0
    for r, p in zip(pre, wl.prompt_lens):
        assert r.args["prompt"] == p and r.args["prompt_bin"] in (16, 32)
        assert r.args["held_tokens"] > 0


def test_paper_model_execution_is_a_span(tmp_path):
    eng = ClusterEngine("mtwnd", DEFAULT_TPU_CELLS[:1], seed=0)
    eng.warmup(max_batch=4)
    eng.configure((1,))
    wl = WorkloadSpec(seed=1, rate_qps=100.0, median_batch=2,
                      max_batch=4).realize(4)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(wl, qos_latency=1.0, keep=(1,))
    recs = tracing.records()
    tracing.clear()
    assert len([r for r in recs if r.name == "live.execute"]) == 4
    assert len([r for r in recs if r.name == "live.serve"]) == 4
    batch, out = eng.kept[1]
    assert out.shape[0] == batch["dense"].shape[0]


def test_launch_serve_runs_an_architecture():
    from repro.launch.serve import serve

    opt, engine = serve(model="deepseek-v2-lite", preset="smoke",
                        n_queries=6, rate_qps=20.0, qos_latency=5.0,
                        budget=2, verbose=False)
    assert engine.llm is not None and len(opt.trace.real) >= 1
    assert all(r.prompt_len > 0 for r in engine.records)


def test_chunked_expert_share_matches_one_pass(monkeypatch):
    from repro.models import layers

    cfg = dataclasses.replace(SMALL, experts_held=SMALL.n_experts)
    params = init_moe_params(jax.random.PRNGKey(13), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 16, cfg.d_model))
    mask = jnp.arange(16)[None, :] < jnp.array([[9], [16]])
    whole, c1 = moe_expert_share(params, x, cfg, mask)
    monkeypatch.setattr(layers, "MOE_CHUNK", 8)
    chunked, c2 = moe_expert_share(params, x, cfg, mask)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_fit_params_casts_to_the_models_dtypes_and_refuses_other_trees():
    own = {"w": jnp.zeros((2, 3), jnp.bfloat16),
           "router": jnp.zeros((3,), jnp.float32)}
    got = fit_params(own, {"w": np.full((2, 3), 0.5, np.float32),
                           "router": np.ones(3)})
    assert got["w"].dtype == jnp.bfloat16 and float(got["w"][0, 0]) == 0.5
    assert got["router"].dtype == jnp.float32
    with pytest.raises(ValueError, match="shape"):
        fit_params(own, {"w": np.ones((2, 4)), "router": np.ones(3)})
    with pytest.raises(ValueError, match="tree"):
        fit_params(own, {"w": np.ones((2, 3))})


def test_engine_serves_the_weights_it_is_handed():
    """``load_params``: every cell type serves the loaded checkpoint, one
    copy shared."""
    from repro.models.paper_models import PAPER_MODELS

    cells = [dataclasses.replace(c, preset="smoke")
             for c in DEFAULT_TPU_CELLS[:2]]
    eng = ClusterEngine("mtwnd", cells, seed=0)
    given = PAPER_MODELS["mtwnd"].init(jax.random.PRNGKey(5), "smoke")
    eng.load_params(given)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng._params["cell1"]),
                                      jax.tree.leaves(eng._params["cell4"])))
    eng.warmup(max_batch=4)
    eng.configure((1, 1))
    wl = WorkloadSpec(seed=2, rate_qps=100.0, median_batch=2,
                      max_batch=4).realize(3)
    eng.serve(wl, qos_latency=1.0, keep=(0, 1, 2))
    for batch, out in eng.kept.values():
        want = PAPER_MODELS["mtwnd"].apply(given, batch)
        np.testing.assert_allclose(out, want, rtol=1e-6)
