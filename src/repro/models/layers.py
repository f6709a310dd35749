"""Core model layers: norms, RoPE, GQA/SWA/MLA attention, MLP, MoE.

Pure-jnp implementations (the Pallas kernels in repro.kernels are drop-in
accelerated equivalents validated against these).  All attention math runs the
softmax in float32 regardless of activation dtype.

Sharding: model code is sharding-agnostic; `repro.launch.sharding.constrain`
is a no-op outside a mesh context and applies with_sharding_constraint inside
one, so the same functions serve smoke tests (1 device) and the 512-chip
dry-run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..launch.sharding import constrain

# --------------------------------------------------------------------------
# norms / simple ops
# --------------------------------------------------------------------------


def rmsnorm(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w + b


def dense(x, w, b=None):
    y = x @ w
    if b is not None:
        y = y + b
    return y


def swiglu_mlp(params, x):
    """SwiGLU MLP: (silu(x W1) * (x W3)) W2."""
    gate = jax.nn.silu(dense(x, params["w1"]))
    up = dense(x, params["w3"])
    h = constrain(gate * up, "batch", None, "model")
    return dense(h, params["w2"])


def gelu_mlp(params, x):
    h = jax.nn.gelu(dense(x, params["w1"], params.get("b1")))
    h = constrain(h, "batch", None, "model")
    return dense(h, params["w2"], params.get("b2"))


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------


def rope_cos_sin(positions, dim, theta, cfg=None):
    """cos/sin tables for rotary embedding.  positions (...,S) int.  A
    ``cfg`` with ``yarn_factor`` set gives YaRN's frequencies and scale."""
    if cfg is not None and cfg.yarn_factor:
        inv_freq = yarn_inv_freq(dim, theta, cfg)
        m = (yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    else:
        inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32)
                                    / dim))
        m = 1.0
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (...,S,dim/2)
    if m == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention scale for a context stretched ``scale`` times."""
    if scale <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, cfg) -> np.ndarray:
    """YaRN inverse frequencies (DeepSeek-V2's ``DeepseekV2YarnRotary
    Embedding``): the original frequencies below the correction range's
    low end (high frequencies, kept), the interpolated ones (divided by
    the factor) above its high end, a linear ramp between."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / base, 1.0 / (cfg.yarn_factor * base)

    def corr_dim(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x, cos, sin):
    """x (..., S, H, hd); cos/sin (..., S, hd/2) — rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# attention core (the ref semantics for kernels/flash_attention)
# --------------------------------------------------------------------------


def attention_core(q, k, v, mask, scale):
    """q (B,S,H,hd), k/v (B,T,K,hd) with H = K*G; mask (B,1,S,T) or (S,T).

    float32 softmax; returns (B,S,H,hd).  Use only for small S (decode /
    smoke) — long sequences go through attention_full.
    """
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    q = q.reshape(b, s, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:  # (B,1,S,T) -> (B,1,1,S,T)
        mask = mask[:, :, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, v.shape[-1])   # value dim may differ (MLA)


def causal_window_mask(q_pos, k_pos, window: int):
    """(…,S,T) bool: causal, optionally sliding-window banded."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window > 0:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return m


# query-block size for the scanned (flash-style) long-sequence path
Q_BLOCK = 1024


def attention_full(q, k, v, q_pos, k_pos, window, scale, causal=True,
                   q_block: int = Q_BLOCK):
    """Full-sequence attention without materializing the (S,T) score matrix.

    q (B,S,H,hd); k/v (B,T,K,hd); q_pos (S,), k_pos (T,) absolute positions.
    For S > q_block the queries are scanned in blocks (the XLA-level
    flash-attention pattern); the per-block mask is built from positions, so
    peak score memory is (B,H,q_block,T) instead of (B,H,S,T).
    """
    b, s = q.shape[:2]
    if s <= q_block or s % q_block != 0:
        mask = causal_window_mask(q_pos[None], k_pos[None], window)[:, None] \
            if causal else jnp.ones((s, k.shape[1]), bool)
        return attention_core(q, k, v, mask, scale)

    nb = s // q_block
    t = k.shape[1]
    # sliding-window banding: a q-block [start, start+qb) only attends to
    # k positions in [start-window+1, start+qb) — slice that static-size band
    # instead of streaming all T keys (8x fewer scores for 32k/4k windows)
    band = window + q_block if (causal and 0 < window) else 0
    use_band = band > 0 and band < t

    def body(_, idx):
        start = idx * q_block
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, start, q_block, axis=0)
        if use_band:
            kstart = jnp.clip(start - window, 0, t - band)
            k_blk = jax.lax.dynamic_slice_in_dim(k, kstart, band, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kstart, band, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, kstart, band, axis=0)
        else:
            k_blk, v_blk, kp = k, v, k_pos
        if causal:
            mask = causal_window_mask(qp[None], kp[None], window)[:, None]
        else:
            mask = jnp.ones((q_block, k_blk.shape[1]), bool)
        return None, attention_core(q_blk, k_blk, v_blk, mask, scale)

    _, blocks = jax.lax.scan(body, None, jnp.arange(nb))
    # blocks (nb, B, q_block, H, hd_v) → (B, S, H, hd_v); note hd_v can
    # differ from q's head dim (MLA values)
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, s, *blocks.shape[3:])
    return out


# --------------------------------------------------------------------------
# GQA attention block (full / sliding window, optional cache)
# --------------------------------------------------------------------------


def init_gqa_params(key, cfg, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * scale).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv * hd)) * scale).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv * hd)) * scale).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def gqa_project_qkv(params, x, cfg, positions):
    """Project + reshape + rope.  x (B,S,D) → q (B,S,H,hd), k/v (B,S,K,hd)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = constrain(dense(x, params["wq"], params.get("bq")), "batch", None, "model")
    k = constrain(dense(x, params["wk"], params.get("bk")), "batch", None, "model")
    v = constrain(dense(x, params["wv"], params.get("bv")), "batch", None, "model")
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def gqa_attention(params, x, cfg, positions):
    """Full-sequence (train/prefill) attention.  positions (S,)."""
    q, k, v = gqa_project_qkv(params, x, cfg, positions)
    out = attention_full(q, k, v, positions, positions, cfg.sliding_window,
                         cfg.d_head ** -0.5)
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.n_heads * cfg.d_head)
    return dense(constrain(out, "batch", None, "model"), params["wo"])


# --------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek/MiniCPM3 style)
# --------------------------------------------------------------------------


def init_mla_params(key, cfg, dtype=jnp.float32):
    """MLA weights; with ``q_lora_rank`` 0 the queries come straight from
    the hidden state through ``wq`` (DeepSeek-V2-Lite), else through the
    low-rank ``wdq`` -> ``q_norm`` -> ``wuq`` path."""
    d, h = cfg.d_model, cfg.n_heads
    qr, r = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    p = {
        "wdkv": (jax.random.normal(ks[2], (d, r)) * s).astype(dtype),
        "wkr": (jax.random.normal(ks[3], (d, rd)) * s).astype(dtype),
        "wuk": (jax.random.normal(ks[4], (r, h * nd)) * r ** -0.5).astype(dtype),
        "wuv": (jax.random.normal(ks[5], (r, h * vd)) * r ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[6], (h * vd, d)) * (h * vd) ** -0.5).astype(dtype),
        "kv_norm": jnp.ones((r,), dtype),
    }
    if qr:
        p["wdq"] = (jax.random.normal(ks[0], (d, qr)) * s).astype(dtype)
        p["wuq"] = (jax.random.normal(ks[1], (qr, h * (nd + rd)))
                    * qr ** -0.5).astype(dtype)
        p["q_norm"] = jnp.ones((qr,), dtype)
    else:
        p["wq"] = (jax.random.normal(ks[0], (d, h * (nd + rd)))
                   * s).astype(dtype)
    return p


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(nope + rope head dim), times YaRN's ``mscale_all_dim`` scale
    squared where the config stretches its rope (DeepSeek-V2)."""
    m = (yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_factor else 1.0)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def mla_latents(params, x, cfg, positions):
    """Per-token latents: c_q (B,S,qr) (x itself with no query
    compression), c_kv (B,S,r), k_rope (B,S,rd)."""
    c_q = (rmsnorm(dense(x, params["wdq"]), params["q_norm"], cfg.norm_eps)
           if cfg.q_lora_rank else x)
    c_kv = rmsnorm(dense(x, params["wdkv"]), params["kv_norm"], cfg.norm_eps)
    k_rope = dense(x, params["wkr"])
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_dim, cfg.rope_theta, cfg)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
    return c_q, c_kv, k_rope


def mla_queries(params, c_q, cfg, positions):
    """q_nope (B,S,H,nd), q_rope (B,S,H,rd)."""
    b, s, _ = c_q.shape
    h, nd, rd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    w = params["wuq"] if cfg.q_lora_rank else params["wq"]
    q = dense(c_q, w).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    cos, sin = rope_cos_sin(positions, rd, cfg.rope_theta, cfg)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _q_block(b: int, h: int) -> int:
    """Query block of the scanned attention: at most 2^17 query rows of
    scores (batch x heads x block) live at once, and no more than
    ``Q_BLOCK``."""
    return int(max(128, min(Q_BLOCK, (1 << 17) // max(b * h, 1))))


def mla_attention(params, x, cfg, positions, return_latents=False):
    """Full-sequence MLA (materializes K/V from latents — train/prefill).
    ``return_latents`` also returns the (c_kv, k_rope) a cache holds."""
    b, s, _ = x.shape
    h, nd, rd, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    c_q, c_kv, k_rope = mla_latents(params, x, cfg, positions)
    q_nope, q_rope = mla_queries(params, c_q, cfg, positions)
    k_nope = dense(c_kv, params["wuk"]).reshape(b, s, h, nd)
    v = dense(c_kv, params["wuv"]).reshape(b, s, h, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (b, s, h, rd))], axis=-1)
    out = attention_full(q, k, v, positions, positions, 0,
                         mla_softmax_scale(cfg), q_block=_q_block(b, h))
    out = dense(out.reshape(b, s, h * vd), params["wo"])
    return (out, c_kv, k_rope) if return_latents else out


def mla_decode_absorbed(params, x, cfg, cache_ckv, cache_krope, valid, pos):
    """Single-token MLA decode in latent space (weight absorption — the
    DeepSeek-V2 trick, which is also the memory-optimal TPU path):

        score_t = q_nope·(W_uk c_t) + q_rope·kr_t
                = (W_uk^T q_nope)·c_t + q_rope·kr_t

    so attention runs against the (r + rd)-dim latent cache directly and the
    per-head value is reconstructed once from the attended latent.

    x (B,1,D); cache_ckv (B,T,r); cache_krope (B,T,rd); valid (T,) or (B,T).
    """
    if valid.ndim == 1:
        valid = valid[None, :]
    b = x.shape[0]
    h, nd, vd, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    c_q, c_kv_new, k_rope_new = mla_latents(params, x, cfg, pos)
    q_nope, q_rope = mla_queries(params, c_q, cfg, pos)       # (B,1,H,·)
    # absorb W_uk: q_lat (B,H,r)
    wuk = params["wuk"].reshape(r, h, nd)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wuk.astype(q_nope.dtype))
    scores = (jnp.einsum("bhr,btr->bht", q_lat, cache_ckv)
              + jnp.einsum("bhd,btd->bht", q_rope[:, 0], cache_krope))
    scores = scores.astype(jnp.float32) * mla_softmax_scale(cfg)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    lat = jnp.einsum("bht,btr->bhr", probs, cache_ckv)        # attended latent
    wuv = params["wuv"].reshape(r, h, vd)
    out = jnp.einsum("bhr,rhd->bhd", lat, wuv.astype(lat.dtype))
    out = out.reshape(b, 1, h * vd)
    return dense(out, params["wo"]), c_kv_new, k_rope_new


# --------------------------------------------------------------------------
# Mixture of Experts (top-k, MegaBlocks-style sort + padded grouped GEMM)
# --------------------------------------------------------------------------


def init_moe_params(key, cfg, dtype=jnp.float32):
    """Router over all ``n_experts`` (float32), the weights of the experts
    held here (``experts_held``, else all) and of the shared experts."""
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_ff
    held = cfg.experts_held or e
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s).astype(jnp.float32),
        "experts": {
            "w1": (jax.random.normal(ks[1], (held, d, f)) * s).astype(dtype),
            "w3": (jax.random.normal(ks[2], (held, d, f)) * s).astype(dtype),
            "w2": (jax.random.normal(ks[3], (held, f, d)) * f ** -0.5).astype(dtype),
        },
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        ka, kb, kc = jax.random.split(jax.random.fold_in(key, 1), 3)
        p["shared"] = {
            "w1": (jax.random.normal(ka, (d, fs)) * s).astype(dtype),
            "w3": (jax.random.normal(kb, (d, fs)) * s).astype(dtype),
            "w2": (jax.random.normal(kc, (fs, d)) * fs ** -0.5).astype(dtype),
        }
    return p


# Tokens an expert-share layer routes in one pass; longer inputs are
# scanned in chunks of this many, which bounds the (tokens x top_k) rows
# of the gathered activations.
MOE_CHUNK = 8192


def moe_expert_share(params, x, cfg, token_mask=None, first_expert=0):
    """Dropless MoE over the experts held here (DeepSeek-V2 semantics).

    Routes every token over all ``n_experts`` (float32 softmax, top-k,
    renormalised only if ``norm_topk_prob``, times ``routed_scaling``),
    then computes only the held experts ``first_expert ..
    first_expert + held - 1`` for the tokens routed to them: the (token,
    expert) pairs are sorted by expert and run through a grouped matmul
    (``lax.ragged_dot``) over the held experts, no capacity buffer and no
    token dropped.  The shared experts are added once per token.  Tokens
    where ``token_mask`` (B,S) is False (padding) get no routed part.

    x (B,S,D) -> (out (B,S,D), counts (held,) int32: the tokens each held
    expert received, the layer's program counter).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    mask = None if token_mask is None else token_mask.reshape(t)
    if t > MOE_CHUNK and t % MOE_CHUNK == 0:
        n = t // MOE_CHUNK
        xs = xt.reshape(n, MOE_CHUNK, d)
        ms = (jnp.ones((n, MOE_CHUNK), bool) if mask is None
              else mask.reshape(n, MOE_CHUNK))
        routed, counts = jax.lax.map(
            lambda a: _routed_share(params, a[0], a[1], cfg, first_expert),
            (xs, ms))
        routed, counts = routed.reshape(t, d), counts.sum(axis=0)
    else:
        routed, counts = _routed_share(params, xt, mask, cfg, first_expert)
    if "shared" in params:
        routed = routed + swiglu_mlp(params["shared"], xt)
    return routed.reshape(b, s, d), counts


def _routed_share(params, xt, mask, cfg, first_expert):
    """The held experts' part for tokens xt (T,D): (out (T,D), counts)."""
    t, d = xt.shape
    k = cfg.top_k
    ew = params["experts"]
    held = ew["w1"].shape[0]
    probs = jax.nn.softmax(dense(xt.astype(jnp.float32), params["router"]),
                           axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, k)                     # (T,k)
    if cfg.norm_topk_prob:
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-20)
    topk_p = topk_p * cfg.routed_scaling
    local = topk_e - first_expert
    here = (local >= 0) & (local < held)
    if mask is not None:
        here &= mask[:, None]
    # (token, expert) pairs sorted by held expert; pairs for experts held
    # elsewhere sort last under the key ``held`` and fall outside every
    # group, so the grouped matmul leaves them zero
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    tok = order // k
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    xs = xt[tok]
    gate = jax.nn.silu(jax.lax.ragged_dot(xs, ew["w1"], counts))
    up = jax.lax.ragged_dot(xs, ew["w3"], counts)
    y = jax.lax.ragged_dot(gate * up, ew["w2"], counts)
    wts = topk_p.reshape(-1)[order]
    y = jnp.where((key[order] < held)[:, None],
                  y * wts[:, None].astype(y.dtype), 0).astype(xt.dtype)
    out = jnp.zeros((t, d), xt.dtype).at[tok].add(y)
    return out, counts


def moe_layer_local(params, x, cfg, capacity_factor: float | None = None):
    """Locality-aware MoE (beyond-paper, for E % model_size != 0):

    tokens are dispatched WITHIN their data shard (`shard_map` over the batch
    axes — no cross-shard token movement, killing the dispatch all-to-all /
    buffer all-reduce of the global path); expert weights stay tensor-parallel
    on the model axis (explicit FSDP all-gather over 'data', psum over
    'model' for the down-projection contraction).
    """
    from ..launch.sharding import active_mesh
    mesh = active_mesh()
    e = cfg.n_experts
    if capacity_factor is None:
        capacity_factor = getattr(cfg, "moe_capacity_factor", 1.25)
    if mesh is None:
        return moe_layer(params, x, cfg, capacity_factor, _global=True)
    from jax.sharding import PartitionSpec as P
    data_axes = tuple(a for a in ("pod", "data")
                      if a in mesh.axis_names and mesh.shape[a] > 1)
    model_sz = mesh.shape.get("model", 1)
    d, f = cfg.d_model, cfg.expert_ff
    fsdp = getattr(cfg, "fsdp", False)
    usable = (data_axes and model_sz > 1 and f % model_sz == 0
              and (not fsdp or d % int(np.prod([mesh.shape[a]
                                                for a in data_axes])) == 0))
    if not usable:
        return moe_layer(params, x, cfg, capacity_factor, _global=True)

    k = cfg.top_k
    dp = int(np.prod([mesh.shape[a] for a in data_axes]))
    b, s, _ = x.shape
    t_local = (b // dp) * s
    capacity = max(min(int(np.ceil(t_local * k / e * capacity_factor)),
                       t_local), k)

    def body(router, w1, w3, w2, xl):
        if fsdp:
            # weights are FSDP-sharded over 'data' only (pod-replicated);
            # tokens shard over all batch axes
            w1 = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        logits = dense(xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_e = jax.lax.top_k(probs, k)
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[topk_e.reshape(-1)].add(1.0) \
            / (t * k)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, data_axes)

        flat_e = topk_e.reshape(-1)
        flat_p = topk_p.reshape(-1).astype(xl.dtype)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        ranks = jnp.cumsum(onehot, axis=0) - onehot
        rank = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
        keep = rank < capacity
        slot = jnp.where(keep, rank, capacity)

        buf = jnp.zeros((e, capacity + 1, d), xl.dtype)
        buf = buf.at[flat_e, slot].add(xt[flat_tok])
        buf = buf[:, :capacity]
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
        up = jnp.einsum("ecd,edf->ecf", buf, w3)
        out_buf = jnp.einsum("ecf,efd->ecd", gate * up, w2)
        # F is model-sharded: complete the contraction
        out_buf = jax.lax.psum(out_buf, "model")
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((e, 1, d), xl.dtype)], axis=1)
        y = out_buf[flat_e, slot] * flat_p[:, None] * keep[:, None].astype(xl.dtype)
        out = jnp.zeros((t, d), xl.dtype).at[flat_tok].add(y)
        return out.reshape(bl, sl, d), aux

    w_specs = (P(None, "data" if fsdp else None, "model"),
               P(None, "data" if fsdp else None, "model"),
               P(None, "model", "data" if fsdp else None))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, None), *w_specs,
                                 P(data_axes, None, None)),
                       out_specs=(P(data_axes, None, None), P()),
                       check_vma=False)
    ew = params["experts"]
    return fn(params["router"], ew["w1"], ew["w3"], ew["w2"], x)


def moe_layer(params, x, cfg, capacity_factor: float | None = None,
              _global: bool = False):
    if not _global and getattr(cfg, "moe_buffer_shard", "none") == "local":
        return moe_layer_local(params, x, cfg, capacity_factor)
    """Top-k MoE with capacity-bounded expert buffers.

    x (B,S,D) → (B,S,D), plus the load-balancing aux loss (Switch-style).

    Dispatch: flatten tokens, route, scatter each (token, expert) pair into a
    per-expert buffer slot (rank within expert, capacity-dropped), run batched
    expert GEMMs (E,C,D)x(E,D,F), and combine with router weights.  With
    experts sharded over 'model' this is expert parallelism: the scatter is
    the all-to-all the roofline sees.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = dense(xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                     # (T,E)
    topk_p, topk_e = jax.lax.top_k(probs, k)                    # (T,k)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (fraction routed vs mean prob per expert)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[topk_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    if capacity_factor is None:
        capacity_factor = getattr(cfg, "moe_capacity_factor", 1.25)
    capacity = min(int(np.ceil(t * k / e * capacity_factor)), t)
    capacity = max(capacity, k)

    flat_e = topk_e.reshape(-1)                                  # (T*k,)
    flat_p = topk_p.reshape(-1).astype(x.dtype)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    # rank of each (token,expert) pair within its expert
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)          # (T*k,E)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot)
    rank = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    keep = rank < capacity
    slot = jnp.where(keep, rank, capacity)                       # overflow → C

    # scatter tokens into (E, C+1, D); slot C is the drop bin
    buf = jnp.zeros((e, capacity + 1, d), x.dtype)
    buf = buf.at[flat_e, slot].add(xt[flat_tok])
    buf = buf[:, :capacity]
    # EP sharding when experts divide the model axis; otherwise the naive
    # baseline replicates the buffer (all-reduce) and the "capacity" perf
    # variant shards the capacity dim instead (reduce-scatter + sharded
    # expert GEMMs) — see EXPERIMENTS.md §Perf
    from ..launch.sharding import active_mesh
    mesh = active_mesh()
    model_size = mesh.shape.get("model", 1) if mesh is not None else 1
    if model_size > 1 and e % model_size == 0:
        buf = constrain(buf, "model", None, None)
    elif getattr(cfg, "moe_buffer_shard", "none") == "capacity":
        buf = constrain(buf, None, "model", None)
    elif getattr(cfg, "moe_buffer_shard", "none") == "capacity2d":
        # capacity dim over data AND model (256-way): dispatch becomes a
        # 2D all-to-all, expert GEMMs fully sharded
        buf = constrain(buf, None, ("data", "model"), None)

    # expert GEMMs
    ew = params["experts"]
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ew["w1"]))
    up = jnp.einsum("ecd,edf->ecf", buf, ew["w3"])
    out_buf = jnp.einsum("ecf,efd->ecd", gate * up, ew["w2"])
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((e, 1, d), x.dtype)], axis=1)        # drop bin

    # gather back and combine with router weights
    y = out_buf[flat_e, slot] * flat_p[:, None] * keep[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[flat_tok].add(y)
    return out.reshape(b, s, d), aux
