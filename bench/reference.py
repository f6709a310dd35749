"""Plain reference of what the benchmark's cells compute.

It imports nothing of the program under test and takes nothing it made.
From a configuration file (instance rates, model profile, QoS limit) and a
stream seed it rebuilds:

* the query stream: arrivals of a Poisson process and lognormal batch
  sizes, drawn from the same threefry bits the stream definition names
  (``split(PRNGKey(seed))``, one ``fold_in`` per chunk of ``chunk``
  queries), but transformed in float64 with no chunking;
* the service time of each query on each instance type, from the
  roofline latency law ``overhead + max(b*flops/(F*eff), (W + b*act)/B)``;
* a first-come-first-served queue, one query at a time: the query takes the
  first idle instance in pool order, or else waits for the instance that
  frees first (the lowest-numbered one on a tie).

``Precision`` names the working precision.  ``F64`` is the reference;
``BF16`` rounds every intermediate result to bfloat16 and is the control
that a correct program must be told apart from.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import ml_dtypes
import numpy as np
from scipy.special import erfinv


@dataclass(frozen=True)
class Precision:
    name: str
    dtype: type
    rnd: Callable[[float], float] | None   # per-operation rounding, or None


F64 = Precision("float64", np.float64, None)
BF16 = Precision("bfloat16", ml_dtypes.bfloat16,
                 lambda x: float(ml_dtypes.bfloat16(x)))


def _unit_floats(bits: np.ndarray) -> np.ndarray:
    """uint32 random bits -> floats in [0, 1): the 23 high bits as the
    mantissa of a number in [1, 2), minus one (exact in float32 and
    float64)."""
    f = ((bits >> np.uint32(9)) | np.uint32(0x3F800000)).view(np.float32)
    return f.astype(np.float64) - 1.0


def stream_bits(seed: int, n: int, chunk: int) -> tuple[np.ndarray,
                                                         np.ndarray]:
    """Raw threefry bits of the first ``n`` queries of stream ``seed``:
    (arrival bits, batch bits), each (n,) uint32, drawn on the host CPU."""
    import jax

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        k_arr, k_batch = jax.random.split(jax.random.PRNGKey(int(seed)))
        n_chunks = -(-n // chunk)
        draw = jax.jit(lambda k, c: jax.random.bits(
            jax.random.fold_in(k, c), (chunk,), np.uint32))
        a = [np.asarray(draw(k_arr, c)) for c in range(n_chunks)]
        b = [np.asarray(draw(k_batch, c)) for c in range(n_chunks)]
    return np.concatenate(a)[:n], np.concatenate(b)[:n]


def stream(seed: int, n: int, law: dict, scale: float = 1.0,
           prec: Precision = F64) -> tuple[np.ndarray, np.ndarray]:
    """(arrival seconds (n,), batch sizes (n,) int) of stream ``seed`` at
    ``scale`` times the rate of ``law`` (a configuration's ``stream``
    block), in precision ``prec``."""
    dt = prec.dtype
    bits_a, bits_b = stream_bits(seed, n, int(law["chunk"]))
    if law["batch_dist"] != "lognormal":
        raise ValueError(f"no reference for batch_dist "
                         f"{law['batch_dist']!r}")
    # u in [0, 1) in ``prec``: rounding to a short mantissa would take the
    # last few draws to 1 (an infinite gap), which a program drawing in
    # that precision never does, so they stop at its largest value below 1.
    below_one = np.nextafter(np.asarray(1, dt), np.asarray(0, dt))
    u = np.minimum(_unit_floats(bits_a).astype(dt), below_one)
    gaps = (-np.log1p(-u)).astype(dt) / np.asarray(law["rate_qps"], dt)
    arrivals = np.cumsum(gaps.astype(dt), dtype=dt) / np.asarray(scale, dt)
    # Batch sizes: uniform on (nextafter(-1, 0), 1), through erfinv.
    lo = float(np.nextafter(np.float32(-1.0), np.float32(0.0)))
    u2 = _unit_floats(bits_b) * (1.0 - lo) + lo
    z = (np.sqrt(2.0) * erfinv(u2)).astype(dt)
    raw = np.exp((np.asarray(math.log(law["median_batch"]), dt)
                  + np.asarray(law["sigma"], dt) * z).astype(dt))
    batches = np.clip(np.round(raw.astype(np.float64)), 1,
                      law["max_batch"]).astype(np.int64)
    return arrivals.astype(np.float64), batches


def service_times(config: dict, batches: np.ndarray,
                  prec: Precision = F64) -> np.ndarray:
    """(n_types, n) seconds of service of each query on each pool type."""
    dt = prec.dtype
    model = config["model"]
    b = np.asarray(batches).astype(dt)
    rows = []
    for t in config["pool"]:
        inst = config["instances"][t]
        eff = model["efficiency"].get(t, 1.0)
        f_eff = np.asarray(inst["flops"] * eff, dt)
        compute = (b * np.asarray(model["flops_per_sample"], dt)).astype(dt) \
            / f_eff
        memory = (np.asarray(model["weight_bytes"], dt)
                  + b * np.asarray(model["act_bytes_per_sample"], dt)
                  ).astype(dt) / np.asarray(inst["mem_bw"], dt)
        rows.append((np.asarray(inst["overhead"], dt)
                     + np.maximum(compute, memory).astype(dt)).astype(dt))
    return np.stack(rows).astype(np.float64)


def fcfs_count(arrivals, service, counts, qos_latency: float,
               prec: Precision = F64) -> int:
    """Queries of the stream served within ``qos_latency`` by the pool
    ``counts`` (instances per type, in pool order), one event at a time."""
    slot_type = [t for t, c in enumerate(counts) for _ in range(int(c))]
    if not slot_type:
        return 0
    rnd = prec.rnd
    free = [0.0] * len(slot_type)
    svc = [list(map(float, row)) for row in service]
    n_slots = len(slot_type)
    ok = 0
    for j, a in enumerate(map(float, arrivals)):
        for s in range(n_slots):
            if free[s] <= a:
                break
        else:
            s = min(range(n_slots), key=free.__getitem__)
        start = a if a >= free[s] else free[s]
        finish = start + svc[slot_type[s]][j]
        if rnd is not None:
            finish = rnd(finish)
        free[s] = finish
        lat = finish - a
        if rnd is not None:
            lat = rnd(lat)
        if lat <= qos_latency:
            ok += 1
    return ok
