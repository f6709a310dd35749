"""Plain reference of DeepSeek-V2-Lite's forward pass, the benchmark's own
copy (the yardstick of ``dsv2lite-live``'s ``logit_err``).

It imports nothing of the program under test.  The architecture comes
from the configuration file's keys (``bench/configs/dsv2lite.json``, the
model's ``config.json`` names), and so do the weights: ``weights`` makes
them from a seed, ``first_k_dense_replace`` dense layers of width
``intermediate_size`` and then MoE layers up to ``num_hidden_layers``,
each with a router over the ``published`` count of routed experts, the
``n_routed_experts`` held here and ``n_shared_experts`` shared experts of
width ``moe_intermediate_size``, and ``vocab_size`` embedding and output
rows.  The program is handed these weights (the kind converts them into
its layout); nothing of the program's own structure is read.
Straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``:

* YaRN explicitly: inverse frequencies from the correction range of
  ``beta_fast``/``beta_slow`` over the original positions, cos/sin scaled
  by ``mscale / mscale_all_dim``, the softmax scale by ``mscale_all_dim``'s
  YaRN scale squared;
* MLA unabsorbed: queries straight from the hidden state, per-head keys
  and values materialised from the normalised KV latent, causal softmax;
* MoE: softmax over all routed experts, top-k without renormalisation
  (``norm_topk_prob`` false), times ``routed_scaling_factor``; the held
  experts' outputs added by a loop over them, weighted by each one's gate
  for the tokens routed to it; the shared experts once per token.

Departures from the published model: only the experts held on this chip
(the first ``n_routed_experts`` of the router's ``published`` count)
contribute, as in the stated deployment's share; the vocabulary is the
held slice; rotary dims use the rotate-half layout (a fixed permutation of
the published interleaved rope columns, the same function under random
weights).

Weights are bfloat16 values (the configuration's precision) with a
float32 router, computed on in float32.  ``quant="float8_e4m3fn"`` is the
control one precision below: every weight and the latent cache (the
normalised KV latent and the rope key) rounded to float8_e4m3fn.

A sequence is scored in one pass at a fixed padded length (causal
attention and per-token experts leave the real positions untouched by the
padding after them), so one program serves every length.
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


class _Key(SimpleNamespace):
    """A hashable architecture (a jit static argument)."""

    def __hash__(self):
        return hash(tuple(sorted(vars(self).items())))

    def __eq__(self, other):
        return vars(self) == vars(other)


def arch(config: dict) -> _Key:
    """The architecture's numbers, from a configuration file's keys."""
    rs = config.get("rope_scaling") or {}
    n_dense = int(config["first_k_dense_replace"])
    return _Key(
        d=int(config["hidden_size"]),
        vocab=int(config["vocab_size"]),
        n_dense=n_dense,
        n_moe=int(config["num_hidden_layers"]) - n_dense,
        d_ff=int(config["intermediate_size"]),
        heads=int(config["num_attention_heads"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v=int(config["v_head_dim"]),
        routed=int(config["published"]["n_routed_experts"]),
        held=int(config["n_routed_experts"]),
        d_expert=int(config["moe_intermediate_size"]),
        shared=int(config["n_shared_experts"]),
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config["norm_topk_prob"]),
        scaling=float(config["routed_scaling_factor"]),
        factor=float(rs.get("factor", 0.0)),
        orig=int(rs.get("original_max_position_embeddings", 0)),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)),
        mscale_all=float(rs.get("mscale_all_dim", 0.0)))


# -- weights from a seed ----------------------------------------------------

def _normal(key, shape, fan_in, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, F32) * fan_in ** -0.5).astype(dtype)


def _norm_weight(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), F32)).astype(
        jnp.bfloat16)


def _swiglu_weights(key, lead, d, f):
    k1, k3, k2 = jax.random.split(key, 3)
    return {"w1": _normal(k1, (*lead, d, f), d),
            "w3": _normal(k3, (*lead, d, f), d),
            "w2": _normal(k2, (*lead, f, d), f)}


def _layer(key, a, moe):
    ks = jax.random.split(key, 10)
    h, r = a.heads, a.kv_rank
    p = {"attn_norm": _norm_weight(ks[0], a.d),
         "mlp_norm": _norm_weight(ks[1], a.d),
         "attn": {"wq": _normal(ks[2], (a.d, h * (a.nope + a.rope)), a.d),
                  "wdkv": _normal(ks[3], (a.d, r), a.d),
                  "wkr": _normal(ks[4], (a.d, a.rope), a.d),
                  "wuk": _normal(ks[5], (r, h * a.nope), r),
                  "wuv": _normal(ks[6], (r, h * a.v), r),
                  "wo": _normal(ks[7], (h * a.v, a.d), h * a.v),
                  "kv_norm": _norm_weight(ks[8], r)}}
    if not moe:
        p["mlp"] = _swiglu_weights(ks[9], (), a.d, a.d_ff)
        return p
    kr, ke, kshared = jax.random.split(ks[9], 3)
    p["moe"] = {"router": _normal(kr, (a.d, a.routed), a.d, F32),
                "experts": _swiglu_weights(ke, (a.held,), a.d, a.d_expert)}
    if a.shared:
        p["moe"]["shared"] = _swiglu_weights(kshared, (), a.d,
                                             a.shared * a.d_expert)
    return p


@partial(jax.jit, static_argnames=("a",))
def _weights(key, a):
    ke, kh, kn, kd, km = jax.random.split(key, 5)
    w = {"embed": (0.02 * jax.random.normal(ke, (a.vocab, a.d), F32)).astype(
             jnp.bfloat16),
         "lm_head": _normal(kh, (a.d, a.vocab), a.d),
         "final_norm": _norm_weight(kn, a.d),
         "dense": jax.vmap(lambda k: _layer(k, a, False))(
             jax.random.split(kd, a.n_dense)),
         "moe": jax.vmap(lambda k: _layer(k, a, True))(
             jax.random.split(km, a.n_moe))}
    return w


def weights(config: dict, seed: int) -> dict:
    """The model's weights, made from ``seed`` with the shapes and counts
    the configuration states: ``embed``, ``lm_head``, ``final_norm``, and
    the stacked layers ``dense`` (``first_k_dense_replace``) and ``moe``
    (the rest of ``num_hidden_layers``)."""
    return _weights(jax.random.PRNGKey(seed), arch(config))


# -- the forward pass -------------------------------------------------------

def _yarn_mscale(scale, mscale):
    if scale <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(a) -> np.ndarray:
    dim, base = a.rope, a.theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exps
    if not a.factor:
        return extra.astype(np.float32)
    inter = 1.0 / (a.factor * base ** exps)

    def corr(rot):
        return dim * math.log(a.orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(a.beta_fast)), 0)
    high = min(math.ceil(corr(a.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rot(x, pos, a):
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq(a))[None]
    m = (_yarn_mscale(a.factor, a.mscale) / _yarn_mscale(a.factor,
                                                         a.mscale_all)
         if a.factor else 1.0)
    c, s = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    h = x.shape[-1] // 2
    return jnp.concatenate([x[..., :h] * c - x[..., h:] * s,
                            x[..., h:] * c + x[..., :h] * s], -1)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _attn(p, x, a, rnd):
    s = x.shape[0]
    h, nd, rd, vd = a.heads, a.nope, a.rope, a.v
    pos = jnp.arange(s)
    q = (x @ p["wq"]).reshape(s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], _rot(q[..., nd:], pos, a)
    c_kv = rnd(_rms(x @ p["wdkv"], p["kv_norm"], a.eps))
    k_rope = rnd(_rot((x @ p["wkr"])[:, None], pos, a)[:, 0])
    k_nope = (c_kv @ p["wuk"]).reshape(s, h, nd)
    v = (c_kv @ p["wuv"]).reshape(s, h, vd)
    scale = (nd + rd) ** -0.5 * (_yarn_mscale(a.factor, a.mscale_all) ** 2
                                 if a.factor else 1.0)
    sc = (jnp.einsum("shd,thd->hst", q_nope, k_nope)
          + jnp.einsum("shd,td->hst", q_rope, k_rope)) * scale
    causal = pos[None, :] <= pos[:, None]
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
    return jnp.einsum("hst,thd->shd", pr, v).reshape(s, h * vd) @ p["wo"]


def _experts(p, x, a):
    probs = jax.nn.softmax(x @ p["router"], -1)
    top_p, top_e = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * a.scaling

    def one(out, e):          # the held experts, one at a time
        gate = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        w = jax.tree.map(lambda t: t[e], p["experts"])
        return out + gate[:, None] * _swiglu(w, x), None

    out = _swiglu(p["shared"], x) if a.shared else jnp.zeros_like(x)
    out, _ = jax.lax.scan(one, out, jnp.arange(a.held))
    return out


def _forward(w, tokens, positions, a, quant):
    if quant is None:
        def rnd(t):
            return t
    else:
        dt = jnp.dtype(quant)

        def rnd(t):
            return t.astype(dt).astype(F32)
    with jax.default_matmul_precision("highest"):
        x = rnd(w["embed"].astype(F32))[tokens]
        for stack, n, moe in (("dense", a.n_dense, False),
                              ("moe", a.n_moe, True)):
            for i in range(n):             # the layers, in order
                lp = jax.tree.map(lambda t: rnd(t[i].astype(F32)), w[stack])
                x = x + _attn(lp["attn"], _rms(x, lp["attn_norm"], a.eps),
                              a, rnd)
                hn = _rms(x, lp["mlp_norm"], a.eps)
                x = x + (_experts(lp["moe"], hn, a) if moe
                         else _swiglu(lp["mlp"], hn))
        head = rnd(w["lm_head"].astype(F32))
        norm = rnd(w["final_norm"].astype(F32))
        return _rms(x[positions], norm, a.eps) @ head


@partial(jax.jit, static_argnames=("a", "quant"))
def _forward_jit(w, tokens, positions, a, quant):
    return _forward(w, tokens, positions, a, quant)


def logits_at(w, tokens, positions, config: dict, length: int,
              quant=None) -> np.ndarray:
    """Float64 logits (len(positions), V) of one sequence at the given
    positions, scored in one pass padded to ``length`` tokens."""
    padded = np.zeros(length, np.int32)
    padded[:len(tokens)] = tokens
    out = _forward_jit(w, jnp.asarray(padded),
                       jnp.asarray(positions, jnp.int32), arch(config), quant)
    return np.asarray(out, np.float64)
