"""Plain reference of MT-WND's forward pass, the benchmark's own copy (the
yardstick of ``mtwnd-live``'s ``out_err``; ``chip_smoke.py`` phase f
holds the same law).

It imports nothing of the program under test.  ``weights`` makes the
model's parameters from a seed and the dimensions the benchmark states
(the traffic's ``model_dims``): ``tables`` (``n_tables`` of ``vocab`` x
``emb``), the shared ``bottom`` MLP, one ``tower`` MLP per task and the
``wide`` part, each MLP a list of ``{"w", "b"}``; the program is handed
them.  ``forward`` takes those weights and the batch the program served
(``dense`` (B, dense), ``cat`` (B, tables, bag) ids), gathers the
embedding rows, sums each bag, and runs the bottom MLP (ReLU, also after
its last layer), the towers and the wide part (ReLU between layers), then
a sigmoid of towers plus wide, on the host in ``dtype`` (float64 is the
reference; bfloat16, every intermediate rounded, the control).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def _mlp(rng, dims) -> list[dict]:
    return [{"w": (rng.standard_normal((a, b), np.float32)
                   * np.float32(a ** -0.5)),
             "b": rng.standard_normal(b, np.float32) * np.float32(0.01)}
            for a, b in zip(dims[:-1], dims[1:])]


def weights(dims: dict, seed: int) -> dict:
    """Float32 weights of the stated dimensions, made from ``seed``."""
    rng = np.random.default_rng(seed)
    tables = [rng.standard_normal((dims["vocab"], dims["emb"]), np.float32)
              * np.float32(0.01) for _ in range(dims["n_tables"])]
    in_dim = dims["dense"] + dims["n_tables"] * dims["emb"]
    bottom = _mlp(rng, [in_dim, *dims["bottom"]])
    towers = [_mlp(rng, [dims["bottom"][-1], *dims["tower"], 1])
              for _ in range(dims["tasks"])]
    wide = _mlp(rng, [in_dim, dims["tasks"]])
    return {"tables": tables, "bottom": bottom, "towers": towers,
            "wide": wide}


def forward(params, batch, dtype=np.float64) -> np.ndarray:
    """(B, tasks) outputs in ``dtype``, returned as float64."""
    import jax

    def cast(a):
        return np.asarray(jax.device_get(a), np.float64).astype(dtype)

    def mlp(layers, x, last_act=False):
        for k, layer in enumerate(layers):
            x = (x @ cast(layer["w"])).astype(dtype) + cast(layer["b"])
            if k < len(layers) - 1 or last_act:
                x = np.maximum(x, np.asarray(0, dtype))
        return x.astype(dtype)

    cat = np.asarray(jax.device_get(batch["cat"]))
    feats = [cast(batch["dense"])]
    for i, table in enumerate(params["tables"]):
        rows = cast(np.asarray(jax.device_get(table))[cat[:, i]])
        feats.append(rows.sum(axis=1, dtype=dtype).astype(dtype))
    x = np.concatenate(feats, axis=-1)
    deep = mlp(params["bottom"], x, last_act=True)
    logits = np.concatenate([mlp(t, deep) for t in params["towers"]], -1)
    z = (logits + mlp(params["wide"], x)).astype(np.float64)
    out = 1.0 / (1.0 + np.exp(-z))
    return out.astype(dtype).astype(np.float64)


BF16 = ml_dtypes.bfloat16
