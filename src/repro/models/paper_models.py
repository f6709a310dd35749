"""The paper's five served models (Table 1), in JAX.

These are the workloads RIBBON serves in its evaluation: CANDLE (multi-tower
MLP + residual tower for drug-response prediction), ResNet50 and VGG19
(conv nets), MT-WND (multi-task wide & deep recommender) and DIEN (GRU +
attention recommender).  The live serving engine (serving/engine.py) executes
them batched; reduced presets keep CPU smoke tests fast.

Each model exposes: init(key, preset) -> params, apply(params, batch) -> out,
and input_spec(preset, batch) for the engine.  The recsys models route their
embedding lookups through kernels.ops.embedding_bag when use_kernel=True.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


def _mlp_params(key, dims, dtype=jnp.float32):
    ks = jax.random.split(key, len(dims) - 1)
    return [{
        "w": (jax.random.normal(k, (a, b)) * a ** -0.5).astype(dtype),
        "b": jnp.zeros((b,), dtype),
    } for k, a, b in zip(ks, dims[:-1], dims[1:])]


def _mlp_apply(layers, x, act=jax.nn.relu, last_act=False):
    for i, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if i < len(layers) - 1 or last_act:
            x = act(x)
    return x


# --------------------------------------------------------------------------
# CANDLE: molecular-feature tower + 2 drug-descriptor towers (shared weights)
# → concatenated → residual prediction tower (paper Fig. 1)
# --------------------------------------------------------------------------

CANDLE_PRESETS = {
    "full": dict(mol_dim=942, drug_dim=3820, tower=1000, depth=3,
                 res_width=1000, res_blocks=3),
    "smoke": dict(mol_dim=32, drug_dim=48, tower=64, depth=2,
                  res_width=64, res_blocks=2),
}


def candle_init(key, preset="smoke", dtype=jnp.float32):
    cfg = CANDLE_PRESETS[preset]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    t = cfg["tower"]
    return {
        "mol_tower": _mlp_params(k1, [cfg["mol_dim"]] + [t] * cfg["depth"], dtype),
        "drug_tower": _mlp_params(k2, [cfg["drug_dim"]] + [t] * cfg["depth"], dtype),
        "res_blocks": [_mlp_params(jax.random.fold_in(k3, i),
                                   [cfg["res_width"]] * 3, dtype)
                       for i in range(cfg["res_blocks"])],
        "merge": _mlp_params(k4, [3 * t, cfg["res_width"]], dtype),
        "head": _mlp_params(jax.random.fold_in(k4, 99), [cfg["res_width"], 1],
                            dtype),
    }


def candle_apply(params, batch):
    """batch = {mol (B,mol_dim), drug1 (B,drug_dim), drug2 (B,drug_dim)}
    → growth prediction (B, 1)."""
    mol = _mlp_apply(params["mol_tower"], batch["mol"], last_act=True)
    d1 = _mlp_apply(params["drug_tower"], batch["drug1"], last_act=True)
    d2 = _mlp_apply(params["drug_tower"], batch["drug2"], last_act=True)
    h = _mlp_apply(params["merge"], jnp.concatenate([mol, d1, d2], axis=-1))
    for blk in params["res_blocks"]:
        h = h + _mlp_apply(blk, jax.nn.relu(h))
    return _mlp_apply(params["head"], jax.nn.relu(h))


def candle_input_spec(preset, batch):
    cfg = CANDLE_PRESETS[preset]
    f = jnp.float32
    return {"mol": jax.ShapeDtypeStruct((batch, cfg["mol_dim"]), f),
            "drug1": jax.ShapeDtypeStruct((batch, cfg["drug_dim"]), f),
            "drug2": jax.ShapeDtypeStruct((batch, cfg["drug_dim"]), f)}


# --------------------------------------------------------------------------
# ResNet50 / VGG19 (lax.conv based)
# --------------------------------------------------------------------------


def _conv_params(key, cin, cout, k, dtype=jnp.float32):
    fan = cin * k * k
    return {"w": (jax.random.normal(key, (k, k, cin, cout)) * fan ** -0.5
                  ).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def _conv(x, p, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


RESNET_PRESETS = {
    # (blocks per stage, base width, img)
    "full": dict(stages=(3, 4, 6, 3), width=64, img=224),
    "smoke": dict(stages=(1, 1, 1, 1), width=8, img=32),
}


def resnet50_init(key, preset="smoke", dtype=jnp.float32):
    cfg = RESNET_PRESETS[preset]
    w = cfg["width"]
    params = {"stem": _conv_params(jax.random.fold_in(key, 0), 3, w, 7, dtype),
              "stages": []}
    cin = w
    for si, n_blocks in enumerate(cfg["stages"]):
        cmid = w * (2 ** si)
        cout = cmid * 4
        stage = []
        for bi in range(n_blocks):
            kk = jax.random.fold_in(key, 100 * si + bi + 1)
            ks = jax.random.split(kk, 4)
            blk = {"c1": _conv_params(ks[0], cin, cmid, 1, dtype),
                   "c2": _conv_params(ks[1], cmid, cmid, 3, dtype),
                   "c3": _conv_params(ks[2], cmid, cout, 1, dtype)}
            if cin != cout:
                blk["proj"] = _conv_params(ks[3], cin, cout, 1, dtype)
            stage.append(blk)
            cin = cout
        params["stages"].append(stage)
    params["head"] = _mlp_params(jax.random.fold_in(key, 999), [cin, 1000],
                                 dtype)
    return params


def resnet50_apply(params, batch):
    x = batch["image"]
    x = jax.nn.relu(_conv(x, params["stem"], stride=2))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            h = jax.nn.relu(_conv(x, blk["c1"], stride=stride))
            h = jax.nn.relu(_conv(h, blk["c2"]))
            h = _conv(h, blk["c3"])
            sc = _conv(x, blk["proj"], stride=stride) if "proj" in blk else x
            x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2))
    return _mlp_apply(params["head"], x)


def resnet50_input_spec(preset, batch):
    img = RESNET_PRESETS[preset]["img"]
    return {"image": jax.ShapeDtypeStruct((batch, img, img, 3), jnp.float32)}


VGG_PRESETS = {
    "full": dict(plan=((64, 2), (128, 2), (256, 4), (512, 4), (512, 4)),
                 img=224, fc=4096),
    "smoke": dict(plan=((8, 1), (16, 1)), img=32, fc=32),
}


def vgg19_init(key, preset="smoke", dtype=jnp.float32):
    cfg = VGG_PRESETS[preset]
    params = {"convs": [], "fc": None}
    cin = 3
    i = 0
    for width, reps in cfg["plan"]:
        group = []
        for _ in range(reps):
            group.append(_conv_params(jax.random.fold_in(key, i), cin, width,
                                      3, dtype))
            cin = width
            i += 1
        params["convs"].append(group)
    feat = cin * (cfg["img"] // (2 ** len(cfg["plan"]))) ** 2
    params["fc"] = _mlp_params(jax.random.fold_in(key, 9999),
                               [feat, cfg["fc"], cfg["fc"], 1000], dtype)
    return params


def vgg19_apply(params, batch):
    x = batch["image"]
    for group in params["convs"]:
        for p in group:
            x = jax.nn.relu(_conv(x, p))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return _mlp_apply(params["fc"], x)


def vgg19_input_spec(preset, batch):
    img = VGG_PRESETS[preset]["img"]
    return {"image": jax.ShapeDtypeStruct((batch, img, img, 3), jnp.float32)}


# --------------------------------------------------------------------------
# MT-WND: embedding tables + shared bottom → per-task towers (CTR, rating...)
# --------------------------------------------------------------------------

MTWND_PRESETS = {
    "full": dict(n_tables=8, vocab=200_000, emb=64, bag=8, dense=13,
                 bottom=(512, 256), tasks=4, tower=(128, 64)),
    "smoke": dict(n_tables=3, vocab=128, emb=16, bag=4, dense=8,
                  bottom=(32, 16), tasks=2, tower=(16, 8)),
}


def mtwnd_init(key, preset="smoke", dtype=jnp.float32):
    cfg = MTWND_PRESETS[preset]
    tables = [
        (jax.random.normal(jax.random.fold_in(key, i),
                           (cfg["vocab"], cfg["emb"])) * 0.01).astype(dtype)
        for i in range(cfg["n_tables"])]
    in_dim = cfg["dense"] + cfg["n_tables"] * cfg["emb"]
    bottom = _mlp_params(jax.random.fold_in(key, 100),
                         [in_dim, *cfg["bottom"]], dtype)
    towers = [
        _mlp_params(jax.random.fold_in(key, 200 + t),
                    [cfg["bottom"][-1], *cfg["tower"], 1], dtype)
        for t in range(cfg["tasks"])]
    wide = _mlp_params(jax.random.fold_in(key, 300), [in_dim, cfg["tasks"]],
                       dtype)
    return {"tables": tables, "bottom": bottom, "towers": towers,
            "wide": wide}


def mtwnd_apply(params, batch, use_kernel=False, interpret=False):
    """batch = {dense (B,dense), cat (B,n_tables,bag) int32} → (B, tasks).

    ``use_kernel`` routes the lookups through the Pallas embedding-bag
    kernel; ``interpret`` runs that kernel in the Pallas interpreter (for
    CPU tests only)."""
    feats = [batch["dense"]]
    for i, table in enumerate(params["tables"]):
        idx = batch["cat"][:, i]
        if use_kernel:
            from ..kernels import ops as kops
            pooled = kops.embedding_bag(idx, table, interpret=interpret)
        else:
            pooled = table[idx].sum(axis=1)
        feats.append(pooled)
    x = jnp.concatenate(feats, axis=-1)
    deep = _mlp_apply(params["bottom"], x, last_act=True)
    task_logits = jnp.concatenate(
        [_mlp_apply(t, deep) for t in params["towers"]], axis=-1)
    wide = _mlp_apply(params["wide"], x)
    return jax.nn.sigmoid(task_logits + wide)


def mtwnd_input_spec(preset, batch):
    cfg = MTWND_PRESETS[preset]
    return {"dense": jax.ShapeDtypeStruct((batch, cfg["dense"]), jnp.float32),
            "cat": jax.ShapeDtypeStruct((batch, cfg["n_tables"], cfg["bag"]),
                                        jnp.int32)}


# --------------------------------------------------------------------------
# DIEN: embeddings + GRU interest extractor + attentional interest evolution
# --------------------------------------------------------------------------

DIEN_PRESETS = {
    "full": dict(vocab=500_000, emb=64, hist=50, hidden=128, dense=13,
                 mlp=(200, 80)),
    "smoke": dict(vocab=128, emb=16, hist=8, hidden=16, dense=8,
                  mlp=(16, 8)),
}


def _gru_params(key, in_dim, hidden, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    def gate(k):
        return {"wx": (jax.random.normal(k, (in_dim, hidden)) * in_dim ** -0.5
                       ).astype(dtype),
                "wh": (jax.random.normal(jax.random.fold_in(k, 1),
                                         (hidden, hidden)) * hidden ** -0.5
                       ).astype(dtype),
                "b": jnp.zeros((hidden,), dtype)}
    return {"r": gate(ks[0]), "z": gate(ks[1]), "h": gate(ks[2])}


def _gru_scan(params, xs, h0):
    def step(h, x):
        r = jax.nn.sigmoid(x @ params["r"]["wx"] + h @ params["r"]["wh"]
                           + params["r"]["b"])
        z = jax.nn.sigmoid(x @ params["z"]["wx"] + h @ params["z"]["wh"]
                           + params["z"]["b"])
        hh = jnp.tanh(x @ params["h"]["wx"] + (r * h) @ params["h"]["wh"]
                      + params["h"]["b"])
        h = (1 - z) * h + z * hh
        return h, h
    hT, hs = jax.lax.scan(step, h0, jnp.moveaxis(xs, 1, 0))
    return hT, jnp.moveaxis(hs, 0, 1)


def dien_init(key, preset="smoke", dtype=jnp.float32):
    cfg = DIEN_PRESETS[preset]
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    table = (jax.random.normal(k1, (cfg["vocab"], cfg["emb"])) * 0.01
             ).astype(dtype)
    in_dim = cfg["dense"] + cfg["emb"] + cfg["hidden"]
    return {
        "table": table,
        "gru1": _gru_params(k2, cfg["emb"], cfg["hidden"], dtype),
        "gru2": _gru_params(k3, cfg["hidden"], cfg["hidden"], dtype),
        "attn": _mlp_params(k4, [cfg["hidden"] + cfg["emb"], 36, 1], dtype),
        "mlp": _mlp_params(k5, [in_dim, *cfg["mlp"], 1], dtype),
    }


def dien_apply(params, batch):
    """batch = {dense (B,d), hist (B,T) int32, target (B,) int32} → CTR (B,1)."""
    hist_emb = params["table"][batch["hist"]]          # (B,T,E)
    tgt_emb = params["table"][batch["target"]]         # (B,E)
    b, t, e = hist_emb.shape
    hidden = params["gru1"]["r"]["wh"].shape[0]
    h0 = jnp.zeros((b, hidden), hist_emb.dtype)
    _, interest = _gru_scan(params["gru1"], hist_emb, h0)   # (B,T,H)
    # attention of target on interest states
    tgt_tile = jnp.broadcast_to(tgt_emb[:, None, :], (b, t, e))
    score_in = jnp.concatenate([interest, tgt_tile], axis=-1)
    scores = _mlp_apply(params["attn"], score_in)[..., 0]   # (B,T)
    att = jax.nn.softmax(scores, axis=-1)
    weighted = interest * att[..., None]
    final_interest, _ = _gru_scan(params["gru2"], weighted, h0)  # AUGRU approx
    x = jnp.concatenate([batch["dense"], tgt_emb, final_interest], axis=-1)
    return jax.nn.sigmoid(_mlp_apply(params["mlp"], x))


def dien_input_spec(preset, batch):
    cfg = DIEN_PRESETS[preset]
    return {"dense": jax.ShapeDtypeStruct((batch, cfg["dense"]), jnp.float32),
            "hist": jax.ShapeDtypeStruct((batch, cfg["hist"]), jnp.int32),
            "target": jax.ShapeDtypeStruct((batch,), jnp.int32)}


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class PaperModel:
    name: str
    init: callable
    apply: callable
    input_spec: callable


PAPER_MODELS = {
    "candle": PaperModel("candle", candle_init, candle_apply,
                         candle_input_spec),
    "resnet50": PaperModel("resnet50", resnet50_init, resnet50_apply,
                           resnet50_input_spec),
    "vgg19": PaperModel("vgg19", vgg19_init, vgg19_apply, vgg19_input_spec),
    "mtwnd": PaperModel("mtwnd", mtwnd_init, mtwnd_apply, mtwnd_input_spec),
    "dien": PaperModel("dien", dien_init, dien_apply, dien_input_spec),
}


_VOCAB_PRESETS = {"mtwnd": MTWND_PRESETS, "dien": DIEN_PRESETS}


def make_random_batch(model_name: str, preset: str, batch: int, seed: int = 0):
    """Random inputs of the preset's shapes.  Categorical ids (MT-WND, DIEN)
    are drawn uniformly from the preset's whole vocabulary, so a full-width
    batch gathers rows from across its tables, not from a small corner."""
    spec = PAPER_MODELS[model_name].input_spec(preset, batch)
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, s in spec.items():
        key, k = jax.random.split(key)
        if np.issubdtype(s.dtype, np.integer):
            hi = _VOCAB_PRESETS[model_name][preset]["vocab"]
            out[name] = jax.random.randint(k, s.shape, 0, hi).astype(s.dtype)
        else:
            out[name] = jax.random.normal(k, s.shape, s.dtype)
    return out
