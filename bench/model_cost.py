"""Operations and bytes of the live DeepSeek-V2-Lite programs, from the
configuration file's architecture (``bench/configs/dsv2lite.json``) and
the counts the program reports with each step.

Both are lower bounds of the work, so that no share computed from them
can pass 1:

* ``prefill_flops`` counts the real requests and prompt tokens, not the
  padding up to a batch bucket or prompt bin: every matmul a token needs
  (MLA with keys and values materialised from the latent, the dense
  layer, the router, the shared experts), the causal attention scores and
  values, the output head at the last position, and the held experts'
  matmuls for the (token, expert) pairs they received (``held_tokens``,
  the prefill's program counter, padding excluded);
* ``decode_bytes`` counts, for each step, every weight outside the
  routed experts once, the weights of the held experts that received a
  token (``active``: held experts with a token, summed over steps and MoE
  layers, the decode loop's program counter) and the latent cache of the
  real requests at its fill.
"""

from __future__ import annotations

BF16_BYTES = 2


def _dims(config: dict) -> dict:
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    nd, rd = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd, r = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    fe = int(config["moe_intermediate_size"])
    return {
        "d": d, "h": h, "nd": nd, "rd": rd, "vd": vd, "r": r,
        "layers": layers, "dense": dense, "moe": layers - dense,
        "vocab": int(config["vocab_size"]),
        "router": int(config["published"]["n_routed_experts"]),
        # parameters a token passes through, per layer
        "attn": d * h * (nd + rd) + d * (r + rd) + r * h * (nd + vd)
        + h * vd * d,
        "mlp": 3 * d * int(config["intermediate_size"]),
        "shared": 3 * d * fe * int(config["n_shared_experts"]),
        "expert": 3 * d * fe,
    }


def prefill_flops(config: dict, rows: int, prompt: int,
                  held_tokens: int) -> float:
    """FLOPs one prefill of ``rows`` requests of ``prompt`` tokens needs."""
    k = _dims(config)
    per_token = 2 * (k["layers"] * k["attn"] + k["dense"] * k["mlp"]
                     + k["moe"] * (k["shared"] + k["d"] * k["router"]))
    causal = prompt * (prompt + 1) / 2
    attn = 2 * k["layers"] * k["h"] * (k["nd"] + k["rd"] + k["vd"]) * causal
    head = 2 * k["d"] * k["vocab"]
    return float(rows * (prompt * per_token + attn + head)
                 + 2 * k["expert"] * held_tokens)


def decode_bytes(config: dict, rows: int, prompt: int, steps: int,
                 active: int) -> float:
    """HBM bytes ``steps`` decode steps of ``rows`` requests after a
    ``prompt``-token prefill must move."""
    k = _dims(config)
    weights = BF16_BYTES * (k["layers"] * k["attn"] + k["dense"] * k["mlp"]
                            + k["moe"] * k["shared"] + k["d"] * k["vocab"])
    router = 4 * k["moe"] * k["d"] * k["router"]          # float32
    fill = steps * prompt + steps * (steps + 1) / 2       # cache tokens read
    cache = BF16_BYTES * rows * k["layers"] * (k["r"] + k["rd"]) * fill
    return float(steps * (weights + router) + BF16_BYTES * k["expert"]
                 * active + cache)


def peaks() -> dict:
    """This device's peaks (``bench/peaks.json``, by ``device_kind``)."""
    import json
    from pathlib import Path

    import jax

    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        return json.load(f)[jax.devices()[0].device_kind]
