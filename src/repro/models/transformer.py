"""Composable model stacks: decoder LMs (dense/MoE/VLM), SSM, hybrid, enc-dec.

Every family exposes the same functional API (``get_model(cfg) -> ModelApi``):

    init_params(key, dtype)                  -> params pytree
    forward(params, tokens, extra)           -> (logits, aux)   full sequence
    loss(params, tokens, labels, extra)      -> scalar
    init_cache(batch, max_len, dtype)        -> cache pytree
    prefill(params, tokens, max_len, extra)  -> (cache, last_logits)
                                                (decoder LMs: also lengths)
    decode_step(params, cache, tokens)       -> (logits, cache)

Layer stacks are scanned over stacked parameters (HLO stays small for the
512-device dry-run compiles); ``cfg.remat`` wraps the scanned block with
jax.checkpoint.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..launch.sharding import constrain
from .cache import (cache_window, dequantize_kv, init_kv_cache,
                    init_mla_cache, init_ssm_cache, quantize_kv)
from .layers import (attention_core, attention_full, dense, gelu_mlp,
                     gqa_attention, gqa_project_qkv, init_gqa_params,
                     init_mla_params, init_moe_params, layernorm,
                     mla_attention, mla_decode_absorbed, mla_latents,
                     moe_expert_share, moe_layer, rmsnorm, swiglu_mlp)
from .ssm import init_ssm_params, ssm_decode_step, ssm_forward


@dataclass(frozen=True)
class ModelApi:
    cfg: ArchConfig
    init_params: Callable
    forward: Callable
    loss: Callable
    init_cache: Callable
    prefill: Callable
    decode_step: Callable


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------


def _init_embed(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    d, v = cfg.d_model, cfg.vocab_size
    return {
        "embed": (jax.random.normal(k1, (v, d)) * 0.02).astype(dtype),
        "lm_head": (jax.random.normal(k2, (d, v)) * d ** -0.5).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
    }


def _stacked(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _logits(params, h, cfg):
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return dense(h, params["lm_head"])


def _lm_loss(forward):
    def loss(params, tokens, labels, extra=None):
        logits, aux = forward(params, tokens, extra)
        logits = logits[:, -labels.shape[1]:]  # drop prefix (VLM patches)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return nll.mean() + 0.01 * aux
    return loss


def _ring_scatter(x, positions, window):
    """x (B,S,...) keyed by absolute positions (S,) = 0..S-1 → ring
    (B,W,...), pos (W,).  Position p lands in slot p mod W; built by
    padding or rolling, with no scatter (the TPU compiler rejects this
    scatter)."""
    s = x.shape[1]
    if s < window:
        pad = [(0, 0), (0, window - s)] + [(0, 0)] * (x.ndim - 2)
        return (jnp.pad(x, pad),
                jnp.pad(positions.astype(jnp.int32), (0, window - s),
                        constant_values=-1))
    shift = (s - window) % window
    return (jnp.roll(x[:, s - window:], shift, axis=1),
            jnp.roll(positions[s - window:].astype(jnp.int32), shift))


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


# --------------------------------------------------------------------------
# decoder LM family: dense / MoE / VLM (stub patch frontend)
# --------------------------------------------------------------------------


def _init_decoder_layer(cfg, dtype, moe=None):
    """Init of one decoder layer; ``moe`` False gives a dense SwiGLU layer
    of width ``d_ff`` (the leading dense layers of a MoE model)."""
    moe = cfg.is_moe if moe is None else moe

    def init(key):
        k1, k2 = jax.random.split(key)
        d = cfg.d_model
        p = {"attn_norm": jnp.ones((d,), dtype),
             "mlp_norm": jnp.ones((d,), dtype)}
        if cfg.attention == "mla":
            p["attn"] = init_mla_params(k1, cfg, dtype)
        else:
            p["attn"] = init_gqa_params(k1, cfg, dtype)
        if moe:
            p["moe"] = init_moe_params(k2, cfg, dtype)
        else:
            k2a, k2b, k2c = jax.random.split(k2, 3)
            d_ff = cfg.d_ff
            s = d ** -0.5
            p["mlp"] = {
                "w1": (jax.random.normal(k2a, (d, d_ff)) * s).astype(dtype),
                "w3": (jax.random.normal(k2b, (d, d_ff)) * s).astype(dtype),
                "w2": (jax.random.normal(k2c, (d_ff, d)) * d_ff ** -0.5).astype(dtype),
            }
        return p
    return init


def _stacks(cfg):
    """(params key, cache key, MoE?) of each layer stack in order: the
    leading dense layers (their cache under ``"lead"``) ahead of the main
    stack (its cache at the top level)."""
    main = ("layers", None, cfg.is_moe)
    return ([("dense_layers", "lead", False), main] if cfg.first_dense_layers
            else [main])


def _cache_keys(cfg):
    if cfg.attention == "mla":
        return ("ckv", "krope")
    if cfg.kv_quant_int8:
        return ("k", "v", "k_scale", "v_scale")
    return ("k", "v")


_NO_COUNTS = np.zeros((0,), np.int32)


def _ffn(cfg, layer_p, hn, moe, mask=None):
    """Feed-forward half of a block: (out, aux loss, held-expert token
    counts; empty unless the layer is an expert share)."""
    if not moe:
        return swiglu_mlp(layer_p["mlp"], hn), jnp.zeros((), jnp.float32), \
            _NO_COUNTS
    if cfg.expert_share:
        out, counts = moe_expert_share(layer_p["moe"], hn, cfg, mask)
        return out, jnp.zeros((), jnp.float32), counts
    out, aux = moe_layer(layer_p["moe"], hn, cfg)
    return out, aux, _NO_COUNTS


def _decoder_block(cfg, layer_p, h, positions, moe=None):
    moe = cfg.is_moe if moe is None else moe
    hn = rmsnorm(h, layer_p["attn_norm"], cfg.norm_eps)
    if cfg.attention == "mla":
        h = h + mla_attention(layer_p["attn"], hn, cfg, positions)
    else:
        h = h + gqa_attention(layer_p["attn"], hn, cfg, positions)
    hn = rmsnorm(h, layer_p["mlp_norm"], cfg.norm_eps)
    out, aux, _ = _ffn(cfg, layer_p, hn, moe)
    return constrain(h + out, "batch", None, None), aux


def _attn_decode_gqa(cfg, attn_p, hn, k_l, v_l, slot, t, valid):
    """Single-token GQA/SWA decode against a ring cache layer."""
    b = hn.shape[0]
    pos_arr = jnp.full((b, 1), t, jnp.int32)
    q, k_new, v_new = gqa_project_qkv(attn_p, hn, cfg, pos_arr)
    k_l = k_l.at[:, slot].set(k_new[:, 0])
    v_l = v_l.at[:, slot].set(v_new[:, 0])
    mask = valid[None, :]                                  # (1,W) → 2d path
    out = attention_core(q, k_l, v_l, mask, cfg.d_head ** -0.5)
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return dense(out, attn_p["wo"]), k_l, v_l


def _attn_decode_gqa_q8(cfg, attn_p, hn, k_l, v_l, ks_l, vs_l, slot, t,
                        valid):
    """int8-KV decode: quantize the new token's K/V, dequantize the cache
    for the attention math (the dequant fuses into the attention dot's
    operand stream on TPU — HBM traffic is the int8 cache)."""
    b = hn.shape[0]
    pos_arr = jnp.full((b, 1), t, jnp.int32)
    q, k_new, v_new = gqa_project_qkv(attn_p, hn, cfg, pos_arr)
    kq, ks = quantize_kv(k_new[:, 0])
    vq, vs = quantize_kv(v_new[:, 0])
    k_l = k_l.at[:, slot].set(kq)
    v_l = v_l.at[:, slot].set(vq)
    ks_l = ks_l.at[:, slot].set(ks)
    vs_l = vs_l.at[:, slot].set(vs)
    k_deq = dequantize_kv(k_l, ks_l, hn.dtype)
    v_deq = dequantize_kv(v_l, vs_l, hn.dtype)
    mask = valid[None, :]
    out = attention_core(q, k_deq, v_deq, mask, cfg.d_head ** -0.5)
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return dense(out, attn_p["wo"]), k_l, v_l, ks_l, vs_l


def _attn_prefill(cfg, attn_p, hn, positions, w):
    """Full-sequence attention of one layer and what its cache layer
    holds: (out, cache arrays in ``_cache_keys`` order, pos table)."""
    s = hn.shape[1]
    if cfg.attention == "mla":
        out, c_kv, k_rope = mla_attention(attn_p, hn, cfg, positions,
                                          return_latents=True)
        ckv_ring, pos_table = _ring_scatter(c_kv, positions, w)
        kr_ring, _ = _ring_scatter(k_rope, positions, w)
        return out, (ckv_ring, kr_ring), pos_table
    q, k, v = gqa_project_qkv(attn_p, hn, cfg, positions)
    out = attention_full(q, k, v, positions, positions, cfg.sliding_window,
                         cfg.d_head ** -0.5)
    out = dense(out.reshape(hn.shape[0], s, cfg.n_heads * cfg.d_head),
                attn_p["wo"])
    k_ring, pos_table = _ring_scatter(k, positions, w)
    v_ring, _ = _ring_scatter(v, positions, w)
    if cfg.kv_quant_int8:
        kq, ksc = quantize_kv(k_ring)
        vq, vsc = quantize_kv(v_ring)
        return out, (kq, vq, ksc, vsc), pos_table
    return out, (k_ring, v_ring), pos_table


def _attn_decode(cfg, attn_p, hn, layers, slot, t, valid):
    """Single-token attention of one layer against its cache layers (in
    ``_cache_keys`` order): (out, updated cache layers)."""
    if cfg.attention == "mla":
        ckv_l, kr_l = layers
        pos_arr = jnp.full((hn.shape[0], 1), t, jnp.int32)
        # write the new token's latents, then attend (absorbed form)
        _, ckv_new, kr_new = mla_latents(attn_p, hn, cfg, pos_arr)
        ckv_l = ckv_l.at[:, slot].set(ckv_new[:, 0].astype(ckv_l.dtype))
        kr_l = kr_l.at[:, slot].set(kr_new[:, 0].astype(kr_l.dtype))
        out, _, _ = mla_decode_absorbed(attn_p, hn, cfg, ckv_l, kr_l, valid,
                                        pos_arr)
        return out, (ckv_l, kr_l)
    if cfg.kv_quant_int8:
        out, *new = _attn_decode_gqa_q8(cfg, attn_p, hn, *layers, slot, t,
                                        valid)
        return out, tuple(new)
    out, k_l, v_l = _attn_decode_gqa(cfg, attn_p, hn, *layers, slot, t, valid)
    return out, (k_l, v_l)


def make_decoder_lm(cfg: ArchConfig) -> ModelApi:
    """Decoder LM.  ``prefill`` takes optional per-row ``lengths`` (B,):
    row b's prompt is its first ``lengths[b]`` tokens (0 marks a padding
    row), every real row has the same length, and decoding continues from
    there; the rest of the row is padding that no cache slot keeps and no
    expert share computes.  An expert-share model's cache carries
    ``held_tokens`` (MoE layers x held experts): the tokens each held
    expert received in the step that made it."""
    is_vlm = cfg.family == "vlm"
    stacks = _stacks(cfg)
    keys = _cache_keys(cfg)

    def init_params(key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params = _init_embed(k1, cfg, dtype)
        n_lead = cfg.first_dense_layers
        params["layers"] = _stacked(_init_decoder_layer(cfg, dtype), k2,
                                    cfg.n_layers - n_lead)
        if n_lead:
            params["dense_layers"] = _stacked(
                _init_decoder_layer(cfg, dtype, moe=False),
                jax.random.fold_in(k2, 1), n_lead)
        return params

    def forward(params, tokens, extra=None):
        h = params["embed"][tokens]
        if is_vlm and extra is not None:
            h = jnp.concatenate([extra.astype(h.dtype), h], axis=1)
        h = constrain(h, "batch", None, None)
        s = h.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        aux = jnp.zeros((), jnp.float32)
        for name, _, moe in stacks:
            def block(h, layer_p, moe=moe):
                return _decoder_block(cfg, layer_p, h, positions, moe)

            h, auxs = jax.lax.scan(_maybe_remat(block, cfg), h, params[name])
            aux = aux + auxs.sum()
        return _logits(params, h, cfg), aux

    def init_cache(batch, max_len, dtype=jnp.bfloat16):
        w = cache_window(cfg, max_len)

        def layers(n):
            if cfg.attention == "mla":
                return init_mla_cache(cfg, n, batch, w, dtype)
            return init_kv_cache(cfg, n, batch, w, dtype)

        cache = layers(cfg.n_layers - cfg.first_dense_layers)
        if cfg.first_dense_layers:
            lead = layers(cfg.first_dense_layers)
            cache["lead"] = {k: lead[k] for k in keys}
        return cache

    def prefill(params, tokens, max_len, extra=None, lengths=None):
        h = params["embed"][tokens]
        if is_vlm and extra is not None:
            h = jnp.concatenate([extra.astype(h.dtype), h], axis=1)
        h = constrain(h, "batch", None, None)
        b, s = h.shape[:2]
        w = cache_window(cfg, max_len)
        positions = jnp.arange(s, dtype=jnp.int32)
        mask = None if lengths is None else positions[None] < lengths[:, None]
        cache = {}
        for name, where, moe in stacks:
            def block(h, layer_p, moe=moe):
                hn = rmsnorm(h, layer_p["attn_norm"], cfg.norm_eps)
                out, arrays, pos_table = _attn_prefill(cfg, layer_p["attn"],
                                                       hn, positions, w)
                h = h + out
                hn = rmsnorm(h, layer_p["mlp_norm"], cfg.norm_eps)
                out, _, counts = _ffn(cfg, layer_p, hn, moe, mask)
                return h + out, (arrays, pos_table, counts)

            h, (arrays, pos_tables, counts) = jax.lax.scan(
                _maybe_remat(block, cfg), h, params[name])
            part = dict(zip(keys, arrays))
            if where is None:
                cache.update(part, pos=pos_tables[0])
                if cfg.expert_share:
                    cache["held_tokens"] = counts
            else:
                cache[where] = part
        if lengths is None:
            cache["t"] = jnp.asarray(s, jnp.int32)
            return cache, _logits(params, h[:, -1:], cfg)
        t = jnp.max(lengths).astype(jnp.int32)
        cache["pos"] = jnp.where(cache["pos"] < t, cache["pos"], -1)
        cache["t"] = t
        cache["rows"] = lengths > 0
        last = jax.lax.dynamic_slice_in_dim(h, t - 1, 1, axis=1)
        return cache, _logits(params, last, cfg)

    def decode_step(params, cache, tokens):
        t = cache["t"]
        h = constrain(params["embed"][tokens], "batch", None, None)
        w = cache[keys[0]].shape[2]
        slot = jnp.mod(t, w)
        pos_table = cache["pos"].at[slot].set(t)
        valid = pos_table >= 0
        rows = cache.get("rows")
        mask = None if rows is None else rows[:, None]
        new_cache = dict(cache, pos=pos_table, t=t + 1)
        for name, where, moe in stacks:
            part = cache if where is None else cache[where]

            def block(h, xs, moe=moe):
                layer_p, layers = xs
                hn = rmsnorm(h, layer_p["attn_norm"], cfg.norm_eps)
                out, layers = _attn_decode(cfg, layer_p["attn"], hn, layers,
                                           slot, t, valid)
                h = h + out
                hn = rmsnorm(h, layer_p["mlp_norm"], cfg.norm_eps)
                out, _, counts = _ffn(cfg, layer_p, hn, moe, mask)
                return h + out, (layers, counts)

            h, (layers, counts) = jax.lax.scan(
                block, h, (params[name], tuple(part[k] for k in keys)))
            if where is None:
                new_cache.update(zip(keys, layers))
                if cfg.expert_share:
                    new_cache["held_tokens"] = counts
            else:
                new_cache[where] = dict(zip(keys, layers))
        return _logits(params, h, cfg), new_cache

    return ModelApi(cfg, init_params, forward, _lm_loss(forward), init_cache,
                    prefill, decode_step)


# --------------------------------------------------------------------------
# SSM family (mamba2)
# --------------------------------------------------------------------------


def make_ssm_lm(cfg: ArchConfig) -> ModelApi:
    def init_layer(key):
        return {"norm": jnp.ones((cfg.d_model,), jnp.float32),
                "ssm": init_ssm_params(key, cfg, jnp.float32)}

    def _cast(p, dtype):
        return jax.tree.map(
            lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, p)

    def init_params(key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params = _init_embed(k1, cfg, dtype)
        params["layers"] = _cast(_stacked(init_layer, k2, cfg.n_layers), dtype)
        return params

    def forward(params, tokens, extra=None):
        h = constrain(params["embed"][tokens], "batch", None, None)

        def block(h, layer_p):
            out, _ = ssm_forward(layer_p["ssm"],
                                 rmsnorm(h, layer_p["norm"], cfg.norm_eps), cfg)
            return h + out, jnp.zeros((), jnp.float32)

        h, _ = jax.lax.scan(_maybe_remat(block, cfg), h, params["layers"])
        return _logits(params, h, cfg), jnp.zeros((), jnp.float32)

    def init_cache(batch, max_len, dtype=jnp.bfloat16):
        return init_ssm_cache(cfg, cfg.n_layers, batch)

    def prefill(params, tokens, max_len, extra=None):
        h = constrain(params["embed"][tokens], "batch", None, None)

        def block(h, layer_p):
            out, carry = ssm_forward(layer_p["ssm"],
                                     rmsnorm(h, layer_p["norm"], cfg.norm_eps),
                                     cfg)
            return h + out, carry

        h, carries = jax.lax.scan(_maybe_remat(block, cfg), h,
                                  params["layers"])
        cache = {"state": carries["state"],
                 "conv": carries["conv"].astype(jnp.float32),
                 "t": jnp.asarray(tokens.shape[1], jnp.int32)}
        return cache, _logits(params, h[:, -1:], cfg)

    def decode_step(params, cache, tokens):
        h = constrain(params["embed"][tokens], "batch", None, None)

        def block(h, xs):
            layer_p, state_l, conv_l = xs
            out, carry = ssm_decode_step(
                layer_p["ssm"], rmsnorm(h, layer_p["norm"], cfg.norm_eps), cfg,
                {"state": state_l, "conv": conv_l.astype(h.dtype)})
            return h + out, (carry["state"], carry["conv"].astype(jnp.float32))

        h, (states, convs) = jax.lax.scan(
            block, h, (params["layers"], cache["state"], cache["conv"]))
        new_cache = {"state": states, "conv": convs, "t": cache["t"] + 1}
        return _logits(params, h, cfg), new_cache

    return ModelApi(cfg, init_params, forward, _lm_loss(forward), init_cache,
                    prefill, decode_step)


# --------------------------------------------------------------------------
# hybrid family (zamba2: mamba2 stack + one shared attention block every k)
# --------------------------------------------------------------------------


def make_hybrid_lm(cfg: ArchConfig) -> ModelApi:
    n_super = cfg.n_layers // cfg.attn_every
    inner = cfg.attn_every

    def init_mamba_layer(key):
        return {"norm": jnp.ones((cfg.d_model,), jnp.float32),
                "ssm": init_ssm_params(key, cfg, jnp.float32)}

    def init_params(key, dtype=jnp.float32):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        params = _init_embed(k1, cfg, dtype)

        def init_super(key):
            return _stacked(init_mamba_layer, key, inner)

        mamba = _stacked(init_super, k2, n_super)
        params["mamba"] = jax.tree.map(
            lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, mamba)
        d, d_ff = cfg.d_model, cfg.d_ff
        s = d ** -0.5
        ka, kb, kc, kd = jax.random.split(k3, 4)
        params["shared"] = {
            "attn_norm": jnp.ones((d,), dtype),
            "attn": init_gqa_params(k4, cfg, dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            "mlp": {
                "w1": (jax.random.normal(ka, (d, d_ff)) * s).astype(dtype),
                "w3": (jax.random.normal(kb, (d, d_ff)) * s).astype(dtype),
                "w2": (jax.random.normal(kc, (d_ff, d)) * d_ff ** -0.5).astype(dtype),
            },
        }
        return params

    def _shared_attn_full(params, h, positions):
        sh = params["shared"]
        h = h + gqa_attention(sh["attn"],
                              rmsnorm(h, sh["attn_norm"], cfg.norm_eps), cfg,
                              positions)
        h = h + swiglu_mlp(sh["mlp"], rmsnorm(h, sh["mlp_norm"], cfg.norm_eps))
        return h

    def forward(params, tokens, extra=None):
        h = constrain(params["embed"][tokens], "batch", None, None)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

        def super_block(h, m_params):
            def mamba_block(h, lp):
                out, _ = ssm_forward(lp["ssm"],
                                     rmsnorm(h, lp["norm"], cfg.norm_eps), cfg)
                return h + out, None
            h, _ = jax.lax.scan(mamba_block, h, m_params)
            h = _shared_attn_full(params, h, positions)
            return h, jnp.zeros((), jnp.float32)

        h, _ = jax.lax.scan(_maybe_remat(super_block, cfg), h, params["mamba"])
        return _logits(params, h, cfg), jnp.zeros((), jnp.float32)

    def init_cache(batch, max_len, dtype=jnp.bfloat16):
        w = cache_window(cfg, max_len)
        kv = init_kv_cache(cfg, n_super, batch, w, dtype, quant=False)
        ssm = init_ssm_cache(cfg, n_super * inner, batch)
        return {"k": kv["k"], "v": kv["v"], "pos": kv["pos"],
                "state": ssm["state"].reshape((n_super, inner) +
                                              ssm["state"].shape[1:]),
                "conv": ssm["conv"].reshape((n_super, inner) +
                                            ssm["conv"].shape[1:]),
                "t": jnp.zeros((), jnp.int32)}

    def prefill(params, tokens, max_len, extra=None):
        h = constrain(params["embed"][tokens], "batch", None, None)
        s = tokens.shape[1]
        w = cache_window(cfg, max_len)
        positions = jnp.arange(s, dtype=jnp.int32)

        def super_block(h, m_params):
            def mamba_block(h, lp):
                out, carry = ssm_forward(
                    lp["ssm"], rmsnorm(h, lp["norm"], cfg.norm_eps), cfg)
                return h + out, carry
            h, carries = jax.lax.scan(mamba_block, h, m_params)
            # shared attention with KV capture
            sh = params["shared"]
            hn = rmsnorm(h, sh["attn_norm"], cfg.norm_eps)
            q, k, v = gqa_project_qkv(sh["attn"], hn, cfg, positions)
            out = attention_full(q, k, v, positions, positions,
                                 cfg.sliding_window, cfg.d_head ** -0.5)
            out = out.reshape(h.shape[0], s, cfg.n_heads * cfg.d_head)
            h = h + dense(out, sh["attn"]["wo"])
            h = h + swiglu_mlp(sh["mlp"],
                               rmsnorm(h, sh["mlp_norm"], cfg.norm_eps))
            k_ring, pos_table = _ring_scatter(k, positions, w)
            v_ring, _ = _ring_scatter(v, positions, w)
            return h, (carries, k_ring, v_ring, pos_table)

        h, (carries, ks, vs, pos_tables) = jax.lax.scan(
            _maybe_remat(super_block, cfg), h, params["mamba"])
        cache = {"k": ks, "v": vs, "pos": pos_tables[0],
                 "state": carries["state"],
                 "conv": carries["conv"].astype(jnp.float32),
                 "t": jnp.asarray(s, jnp.int32)}
        return cache, _logits(params, h[:, -1:], cfg)

    def decode_step(params, cache, tokens):
        t = cache["t"]
        h = constrain(params["embed"][tokens], "batch", None, None)
        w = cache["k"].shape[2]
        slot = jnp.mod(t, w)
        pos_table = cache["pos"].at[slot].set(t)
        valid = pos_table >= 0

        def super_block(h, xs):
            m_params, state_s, conv_s, k_l, v_l = xs

            def mamba_block(h, inner_xs):
                lp, state_l, conv_l = inner_xs
                out, carry = ssm_decode_step(
                    lp["ssm"], rmsnorm(h, lp["norm"], cfg.norm_eps), cfg,
                    {"state": state_l, "conv": conv_l.astype(h.dtype)})
                return h + out, (carry["state"],
                                 carry["conv"].astype(jnp.float32))

            h, (states, convs) = jax.lax.scan(mamba_block, h,
                                              (m_params, state_s, conv_s))
            sh = params["shared"]
            hn = rmsnorm(h, sh["attn_norm"], cfg.norm_eps)
            out, k_l, v_l = _attn_decode_gqa(cfg, sh["attn"], hn, k_l, v_l,
                                             slot, t, valid)
            h = h + out
            h = h + swiglu_mlp(sh["mlp"],
                               rmsnorm(h, sh["mlp_norm"], cfg.norm_eps))
            return h, (states, convs, k_l, v_l)

        h, (states, convs, ks, vs) = jax.lax.scan(
            super_block, h,
            (params["mamba"], cache["state"], cache["conv"], cache["k"],
             cache["v"]))
        new_cache = {"k": ks, "v": vs, "pos": pos_table, "state": states,
                     "conv": convs, "t": t + 1}
        return _logits(params, h, cfg), new_cache

    return ModelApi(cfg, init_params, forward, _lm_loss(forward), init_cache,
                    prefill, decode_step)


# --------------------------------------------------------------------------
# encoder-decoder family (whisper-style; stub frame frontend)
# --------------------------------------------------------------------------


def make_encdec_lm(cfg: ArchConfig) -> ModelApi:
    def init_enc_layer(key):
        k1, k2 = jax.random.split(key)
        d, d_ff = cfg.d_model, cfg.d_ff
        s = d ** -0.5
        ka, kb = jax.random.split(k2)
        return {
            "norm1_w": jnp.ones((d,), jnp.float32),
            "norm1_b": jnp.zeros((d,), jnp.float32),
            "attn": init_gqa_params(k1, cfg, jnp.float32),
            "norm2_w": jnp.ones((d,), jnp.float32),
            "norm2_b": jnp.zeros((d,), jnp.float32),
            "mlp": {"w1": jax.random.normal(ka, (d, d_ff)) * s,
                    "b1": jnp.zeros((d_ff,)),
                    "w2": jax.random.normal(kb, (d_ff, d)) * d_ff ** -0.5,
                    "b2": jnp.zeros((d,))},
        }

    def init_dec_layer(key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = init_enc_layer(k1)
        p["xattn"] = init_gqa_params(k2, cfg, jnp.float32)
        p["norm3_w"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["norm3_b"] = jnp.zeros((cfg.d_model,), jnp.float32)
        return p

    def _cast(p, dtype):
        return jax.tree.map(lambda x: x.astype(dtype), p)

    def init_params(key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        params = _init_embed(k1, cfg, dtype)
        params["enc_layers"] = _cast(
            _stacked(init_enc_layer, k2, cfg.n_encoder_layers), dtype)
        params["dec_layers"] = _cast(
            _stacked(init_dec_layer, k3, cfg.n_layers), dtype)
        return params

    def _enc_block(h, layer_p, positions):
        hn = layernorm(h, layer_p["norm1_w"], layer_p["norm1_b"], cfg.norm_eps)
        q, k, v = gqa_project_qkv(layer_p["attn"], hn, cfg, positions)
        out = attention_full(q, k, v, positions, positions, 0,
                             cfg.d_head ** -0.5, causal=False)
        out = out.reshape(h.shape[0], h.shape[1], cfg.n_heads * cfg.d_head)
        h = h + dense(out, layer_p["attn"]["wo"])
        hn = layernorm(h, layer_p["norm2_w"], layer_p["norm2_b"], cfg.norm_eps)
        return h + gelu_mlp(layer_p["mlp"], hn)

    def encode(params, frames):
        h = constrain(frames, "batch", None, None)
        positions = jnp.arange(frames.shape[1], dtype=jnp.int32)

        def block(h, layer_p):
            return _enc_block(h, layer_p, positions), None

        h, _ = jax.lax.scan(_maybe_remat(block, cfg), h, params["enc_layers"])
        return h

    def _cross_attn(layer_p, hn, enc_k, enc_v):
        b, s = hn.shape[:2]
        p = layer_p["xattn"]
        q = dense(hn, p["wq"], p.get("bq")).reshape(b, s, cfg.n_heads,
                                                    cfg.d_head)
        out = attention_full(q, enc_k, enc_v,
                             jnp.arange(s, dtype=jnp.int32),
                             jnp.arange(enc_k.shape[1], dtype=jnp.int32),
                             0, cfg.d_head ** -0.5, causal=False)
        return dense(out.reshape(b, s, cfg.n_heads * cfg.d_head), p["wo"])

    def _dec_block(cfg_, layer_p, h, positions, enc_h):
        hn = layernorm(h, layer_p["norm1_w"], layer_p["norm1_b"], cfg.norm_eps)
        h = h + gqa_attention(layer_p["attn"], hn, cfg_, positions)
        hn = layernorm(h, layer_p["norm3_w"], layer_p["norm3_b"], cfg.norm_eps)
        b, t = enc_h.shape[:2]
        p = layer_p["xattn"]
        enc_k = dense(enc_h, p["wk"], p.get("bk")).reshape(
            b, t, cfg.n_kv_heads, cfg.d_head)
        enc_v = dense(enc_h, p["wv"], p.get("bv")).reshape(
            b, t, cfg.n_kv_heads, cfg.d_head)
        h = h + _cross_attn(layer_p, hn, enc_k, enc_v)
        hn = layernorm(h, layer_p["norm2_w"], layer_p["norm2_b"], cfg.norm_eps)
        return h + gelu_mlp(layer_p["mlp"], hn)

    def forward(params, tokens, extra=None):
        """tokens: decoder ids (B,S); extra: frame embeddings (B,T,D)."""
        enc_h = encode(params, extra)
        h = constrain(params["embed"][tokens], "batch", None, None)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

        def block(h, layer_p):
            return _dec_block(cfg, layer_p, h, positions, enc_h), None

        h, _ = jax.lax.scan(_maybe_remat(block, cfg), h, params["dec_layers"])
        return _logits(params, h, cfg), jnp.zeros((), jnp.float32)

    def init_cache(batch, max_len, dtype=jnp.bfloat16):
        w = cache_window(cfg, max_len)
        kv = init_kv_cache(cfg, cfg.n_layers, batch, w, dtype, quant=False)
        kv["enc_k"] = jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq,
                                 cfg.n_kv_heads, cfg.d_head), dtype)
        kv["enc_v"] = jnp.zeros_like(kv["enc_k"])
        return kv

    def prefill(params, tokens, max_len, extra=None):
        enc_h = encode(params, extra)
        h = constrain(params["embed"][tokens], "batch", None, None)
        s = tokens.shape[1]
        w = cache_window(cfg, max_len)
        positions = jnp.arange(s, dtype=jnp.int32)

        def block(h, layer_p):
            hn = layernorm(h, layer_p["norm1_w"], layer_p["norm1_b"],
                           cfg.norm_eps)
            q, k, v = gqa_project_qkv(layer_p["attn"], hn, cfg, positions)
            out = attention_full(q, k, v, positions, positions,
                                 cfg.sliding_window, cfg.d_head ** -0.5)
            out = out.reshape(h.shape[0], s, cfg.n_heads * cfg.d_head)
            h = h + dense(out, layer_p["attn"]["wo"])
            # cross attention (+ capture enc K/V)
            hn = layernorm(h, layer_p["norm3_w"], layer_p["norm3_b"],
                           cfg.norm_eps)
            b, t = enc_h.shape[:2]
            p = layer_p["xattn"]
            enc_k = dense(enc_h, p["wk"], p.get("bk")).reshape(
                b, t, cfg.n_kv_heads, cfg.d_head)
            enc_v = dense(enc_h, p["wv"], p.get("bv")).reshape(
                b, t, cfg.n_kv_heads, cfg.d_head)
            h = h + _cross_attn(layer_p, hn, enc_k, enc_v)
            hn = layernorm(h, layer_p["norm2_w"], layer_p["norm2_b"],
                           cfg.norm_eps)
            h = h + gelu_mlp(layer_p["mlp"], hn)
            k_ring, pos_table = _ring_scatter(k, positions, w)
            v_ring, _ = _ring_scatter(v, positions, w)
            return h, (k_ring, v_ring, enc_k, enc_v, pos_table)

        h, (ks, vs, eks, evs, pos_tables) = jax.lax.scan(
            _maybe_remat(block, cfg), h, params["dec_layers"])
        cache = {"k": ks, "v": vs, "enc_k": eks, "enc_v": evs,
                 "pos": pos_tables[0], "t": jnp.asarray(s, jnp.int32)}
        return cache, _logits(params, h[:, -1:], cfg)

    def decode_step(params, cache, tokens):
        t = cache["t"]
        h = constrain(params["embed"][tokens], "batch", None, None)
        w = cache["k"].shape[2]
        slot = jnp.mod(t, w)
        pos_table = cache["pos"].at[slot].set(t)
        valid = pos_table >= 0

        def block(h, xs):
            layer_p, k_l, v_l, ek_l, ev_l = xs
            hn = layernorm(h, layer_p["norm1_w"], layer_p["norm1_b"],
                           cfg.norm_eps)
            out, k_l, v_l = _attn_decode_gqa(cfg, layer_p["attn"], hn, k_l,
                                             v_l, slot, t, valid)
            h = h + out
            hn = layernorm(h, layer_p["norm3_w"], layer_p["norm3_b"],
                           cfg.norm_eps)
            h = h + _cross_attn(layer_p, hn, ek_l, ev_l)
            hn = layernorm(h, layer_p["norm2_w"], layer_p["norm2_b"],
                           cfg.norm_eps)
            h = h + gelu_mlp(layer_p["mlp"], hn)
            return h, (k_l, v_l)

        h, (ks, vs) = jax.lax.scan(
            block, h, (params["dec_layers"], cache["k"], cache["v"],
                       cache["enc_k"], cache["enc_v"]))
        new_cache = dict(cache, k=ks, v=vs, pos=pos_table, t=t + 1)
        return _logits(params, h, cfg), new_cache

    def loss(params, tokens, labels, extra=None):
        logits, aux = forward(params, tokens, extra)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return nll.mean()

    return ModelApi(cfg, init_params, forward, loss, init_cache, prefill,
                    decode_step)


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------


def get_model(cfg: ArchConfig) -> ModelApi:
    if cfg.family in ("dense", "moe", "vlm"):
        return make_decoder_lm(cfg)
    if cfg.family == "ssm":
        return make_ssm_lm(cfg)
    if cfg.family == "hybrid":
        return make_hybrid_lm(cfg)
    if cfg.family == "encdec":
        return make_encdec_lm(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")
