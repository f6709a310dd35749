"""Plain reference of DeepSeek-V2(-Lite)'s forward pass.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
description (DeepSeek-V2, arXiv:2405.04434, and the model's
``modeling_deepseek.py``) and independent of ``repro.models``: no cache,
no kernels, no batching tricks.  It reads the program's parameter pytree
(``embed``, ``lm_head``, ``final_norm``, ``dense_layers``, ``layers``)
and a config with ``ArchConfig``'s field names.

* YaRN is computed explicitly: inverse frequencies from the correction
  range of ``beta_fast``/``beta_slow`` over the original positions, the
  cos/sin scaled by ``mscale / mscale_all_dim``, and the softmax scale
  multiplied by ``mscale_all_dim``'s YaRN scale squared.
* MLA in its unabsorbed form: per-head keys and values are materialised
  from the normalised KV latent, queries come straight from the hidden
  state (no query compression).
* The MoE layer routes over every expert (softmax, top-k, renormalised
  only where ``norm_topk_prob``, times ``routed_scaling``) and adds, by a
  loop over the held experts, each one's output weighted by its gate for
  the tokens routed to it; the shared experts are added once per token.

Departures from the published model:

* Only the experts held here (the first ``experts_held``) contribute: the
  parts of the absent experts, which other chips compute in the stated
  deployment, are left out, as in the program.
* The vocabulary is the slice held here (``vocab_size`` rows of the
  chip's share).
* Rotary dims use the rotate-half layout.  The published checkpoint
  stores them interleaved and permutes them before rotating; that is a
  fixed permutation of the rope columns of the query and rope-key
  projections, the same function under random weights.
* Weights are whatever the caller passes (random, from a seed).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over the rope dims."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** exps
    if not cfg.yarn_factor:
        return freq_extra.astype(np.float32)
    freq_inter = 1.0 / (cfg.yarn_factor * base ** exps)

    def correction_dim(num_rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extra_share = 1.0 - ramp
    inv = freq_inter * (1 - extra_share) + freq_extra * extra_share
    return inv.astype(np.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, cfg):
    """x (S, H, rd) rotated at ``positions`` (S,) (rotate-half)."""
    inv = jnp.asarray(yarn_inv_freq(cfg))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_factor else 1.0)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _mla(p, x, cfg):
    """Causal MLA of one sequence x (S, D), unabsorbed."""
    s = x.shape[0]
    h, nd, rd, vd = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    pos = jnp.arange(s)
    q = (x @ p["wq"]).reshape(s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], _rope(q[..., nd:], pos, cfg)
    c_kv = _rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope((x @ p["wkr"])[:, None, :], pos, cfg)       # (S,1,rd)
    k_nope = (c_kv @ p["wuk"]).reshape(s, h, nd)
    v = (c_kv @ p["wuv"]).reshape(s, h, vd)
    scale = (nd + rd) ** -0.5
    if cfg.yarn_factor:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    scores = (jnp.einsum("shd,thd->hst", q_nope, k_nope)
              + jnp.einsum("shd,td->hst", q_rope, k_rope[:, 0])) * scale
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(s, h * vd)
    return out @ p["wo"]


def moe(p, x, cfg):
    """The held experts' part plus the shared experts, x (S, D)."""
    probs = jax.nn.softmax(x @ p["router"], -1)                 # (S, E)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * cfg.routed_scaling
    ex = p["experts"]
    out = jnp.zeros_like(x)
    for e in range(ex["w1"].shape[0]):
        gate = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)   # (S,)
        y = _swiglu({k: ex[k][e] for k in ("w1", "w3", "w2")}, x)
        out = out + gate[:, None] * y
    if "shared" in p:
        out = out + _swiglu(p["shared"], x)
    return out


def _layer(p, x, cfg, is_moe: bool):
    x = x + _mla(p["attn"], _rmsnorm(x, p["attn_norm"], cfg.norm_eps), cfg)
    hn = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    return x + (moe(p["moe"], hn, cfg) if is_moe else _swiglu(p["mlp"], hn))


def _unstack(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def forward(params, tokens, cfg):
    """Logits (S, V) over every position of one sequence ``tokens`` (S,),
    in float32 at the highest matmul precision."""
    p = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        stacks = [("dense_layers", False), ("layers", True)]
        for name, is_moe in stacks:
            if name not in p:
                continue
            n = jax.tree.leaves(p[name])[0].shape[0]
            for i in range(n):
                x = _layer(_unstack(p[name], i), x, cfg, is_moe)
        x = _rmsnorm(x, p["final_norm"], cfg.norm_eps)
        return x @ p["lm_head"]
