"""Query-stream workload generation (paper §5.1).

* Query inter-arrival times follow a **Poisson process** (exponential
  inter-arrival), as in DeepRecSys / MLPerf-inference and the other works the
  paper cites.
* Batch sizes follow a **heavy-tail log-normal** distribution (the paper's
  default, after DeepRecSys), with a **Gaussian** alternative used for the
  robustness study (paper Fig. 11).

Generation is jax.random-based so streams are reproducible from a single seed
across the whole framework.

Two stream representations coexist:

* :class:`Workload` — a host-materialized finite trace (arrays), the classic
  representation every simulator lane binds to.
* :class:`WorkloadSpec` — a *generative* description of an unbounded stream:
  fixed-size query chunks are drawn **on device** (threefry keys split per
  chunk index), so a streaming consumer never materializes the episode.
  ``realize(n)`` runs the identical chunked computation and concatenates the
  results, which is what makes a streamed episode bit-identical to a
  monolithic scan over the realized trace — threefry bits depend on the draw
  shape, so the chunked generation *is* the canonical stream and the
  monolithic path replays it chunk for chunk.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class RequestBucket:
    """One cell of a request-size distribution (Mélange's 2D histogram).

    A bucket scales the analytical per-query resource model
    multiplicatively: ``flops_scale`` multiplies the model's
    ``flops_per_sample`` (output-size axis — longer outputs cost compute)
    and ``bytes_scale`` multiplies ``act_bytes_per_sample`` (input-size
    axis — bigger inputs move more activation bytes).  ``rate`` is the
    bucket's share of the arrival rate in queries/s.  The unit bucket
    ``(1.0, 1.0)`` reproduces the un-bucketed model bit for bit (a float
    multiply by 1.0 is exact).
    """

    name: str
    rate: float
    flops_scale: float = 1.0
    bytes_scale: float = 1.0


@dataclass(frozen=True)
class Workload:
    """A concrete query stream."""

    arrivals: np.ndarray      # (n,) absolute arrival times, seconds, sorted
    batches: np.ndarray       # (n,) int batch size per query
    rate_qps: float           # nominal arrival rate
    # Request-size bucket annotation (None = legacy scalar stream): the
    # bucket index of each query plus the bucket descriptors.  Simulator
    # lanes build bucket-aware service tables from these; everything else
    # (arrivals, batches, scaling) is bucket-agnostic.
    bucket_of: np.ndarray | None = None    # (n,) int bucket index per query
    buckets: tuple[RequestBucket, ...] | None = None

    @property
    def n_queries(self) -> int:
        return len(self.arrivals)

    def scaled(self, load_factor: float) -> "Workload":
        """Same query sequence under a different load level (paper §5.5:
        'the load becomes 1.5 times heavier' compresses inter-arrivals)."""
        return Workload(arrivals=self.arrivals / load_factor,
                        batches=self.batches,
                        rate_qps=self.rate_qps * load_factor,
                        bucket_of=self.bucket_of, buckets=self.buckets)


def lognormal_batches(key, n: int, median: float = 24.0, sigma: float = 0.8,
                      max_batch: int = 256) -> jnp.ndarray:
    """Heavy-tail log-normal batch sizes, clipped to [1, max_batch]."""
    z = jax.random.normal(key, (n,))
    raw = jnp.exp(jnp.log(median) + sigma * z)
    return jnp.clip(jnp.round(raw), 1, max_batch).astype(jnp.int32)


def gaussian_batches(key, n: int, mean: float = 48.0, std: float = 24.0,
                     max_batch: int = 256) -> jnp.ndarray:
    """Gaussian batch sizes (paper Fig. 11 robustness study)."""
    raw = mean + std * jax.random.normal(key, (n,))
    return jnp.clip(jnp.round(raw), 1, max_batch).astype(jnp.int32)


@partial(jax.jit, static_argnames=("chunk", "dist"))
def _spec_chunk(k_arr, k_batch, c, base, rate, scale, p_a, p_b, max_batch, *,
                chunk: int, dist: str):
    """One on-device query chunk: (scaled arrivals f32, unscaled local
    arrivals f32, batches i32).

    Every float expression carries an explicit float32 dtype — the caller
    runs this under ``jax.enable_x64`` so the load-scale
    division happens in float64 (matching the host path, which divides
    float64 arrivals before the device's float32 cast), and x64 mode flips
    jax's *default* dtypes, so nothing here may rely on them.  Chunk ``c``
    draws from ``fold_in(key, c)``, so any chunk regenerates independently
    given the previous chunk's last unscaled arrival (``base``).
    """
    ka = jax.random.fold_in(k_arr, c)
    kb = jax.random.fold_in(k_batch, c)
    gaps = jax.random.exponential(ka, (chunk,), dtype=jnp.float32) / rate
    local = base + jnp.cumsum(gaps)
    arr = (local.astype(jnp.float64) / scale.astype(jnp.float64)).astype(
        jnp.float32)
    z = jax.random.normal(kb, (chunk,), dtype=jnp.float32)
    raw = jnp.exp(p_a + p_b * z) if dist == "lognormal" else p_a + p_b * z
    batches = jnp.clip(jnp.round(raw), jnp.float32(1.0),
                       max_batch).astype(jnp.int32)
    return arr, local, batches


@dataclass(frozen=True)
class WorkloadSpec:
    """Generative description of an unbounded query stream.

    The stream is defined *chunk-wise*: chunk ``c`` (``chunk`` queries) is
    drawn on device from ``fold_in``-derived keys, inter-arrival gaps
    accumulating onto the previous chunk's last unscaled arrival.  ``scale``
    compresses arrivals exactly as ``Workload.scaled`` does — the division
    runs in float64 before any float32 cast, so a streamed scaled episode
    matches a host-built scaled trace bit for bit.  ``scaled`` composes
    multiplicatively, mirroring ``Workload.scaled`` chaining.
    """

    seed: int
    rate_qps: float
    batch_dist: str = "lognormal"
    chunk: int = 4096
    scale: float = 1.0
    median_batch: float = 24.0
    sigma: float = 0.8
    mean_batch: float = 48.0
    std_batch: float = 24.0
    max_batch: int = 256

    def __post_init__(self):
        if self.chunk < 1:
            raise ValueError("chunk must be >= 1")
        if not self.rate_qps > 0 or not self.scale > 0:
            raise ValueError("rate_qps and scale must be > 0")
        if self.batch_dist not in ("lognormal", "gaussian"):
            raise ValueError(f"unknown batch_dist {self.batch_dist!r}")

    @property
    def effective_rate(self) -> float:
        """Nominal arrival rate after load scaling."""
        return self.rate_qps * self.scale

    def scaled(self, load_factor: float) -> "WorkloadSpec":
        """Same stream under ``load_factor``-times heavier traffic
        (``Workload.scaled`` semantics; factors compose by multiplication,
        and the realized division is ``unscaled / (f1 * f2 * ...)``)."""
        if not load_factor > 0:
            raise ValueError("load_factor must be > 0")
        return replace(self, scale=self.scale * float(load_factor))

    def _keys(self):
        return jax.random.split(jax.random.PRNGKey(self.seed))

    def generate_chunk(self, c: int, base: float):
        """Device arrays of chunk ``c``: (scaled arrivals f32, unscaled
        local arrivals f32, batches i32).  ``base`` is the previous chunk's
        last *unscaled* arrival (0.0 for chunk 0, or a rebased origin)."""
        k_arr, k_batch = self._keys()
        if self.batch_dist == "lognormal":
            p_a = float(np.log(self.median_batch))
            p_b = self.sigma
        else:
            p_a = self.mean_batch
            p_b = self.std_batch
        with jax.enable_x64(True):
            return _spec_chunk(
                k_arr, k_batch, np.int64(c), jnp.float32(base),
                jnp.float32(self.rate_qps), jnp.float32(self.scale),
                jnp.float32(p_a), jnp.float32(p_b),
                jnp.float32(self.max_batch),
                chunk=self.chunk, dist=self.batch_dist)

    def realize(self, n_queries: int) -> Workload:
        """Host :class:`Workload` of the stream's first ``n_queries`` — the
        *same* chunked device computation, concatenated and truncated.

        Unscaled float32 arrivals are upcast to float64 exactly, then the
        load scale divides in float64 (one division by the composed scale)
        — so a ``PoolSimulator`` bound to the result sees, after its own
        float32 cast, the identical bits a streaming consumer generates on
        device.
        """
        if n_queries < 0:
            raise ValueError("n_queries must be >= 0")
        arrs: list[np.ndarray] = []
        bats: list[np.ndarray] = []
        base = 0.0
        for c in range(math.ceil(n_queries / self.chunk)):
            _, local, batches = self.generate_chunk(c, base)
            local = np.asarray(jax.device_get(local))
            arrs.append(local)
            bats.append(np.asarray(jax.device_get(batches)))
            base = float(local[-1])
        arr64 = (np.concatenate(arrs)[:n_queries].astype(np.float64)
                 if arrs else np.zeros(0, dtype=np.float64))
        if self.scale != 1.0:
            arr64 = arr64 / np.float64(self.scale)
        bat64 = (np.concatenate(bats)[:n_queries].astype(np.int64)
                 if bats else np.zeros(0, dtype=np.int64))
        return Workload(arrivals=arr64, batches=bat64,
                        rate_qps=float(self.effective_rate))


# Distinct fold_in tag deriving the bucket draw stream from the spec seed:
# the arrival/batch keys come from split(PRNGKey(seed)), so folding the raw
# seed key with a fixed tag gives buckets their own threefry stream without
# perturbing a single bit of the base arrival/batch draws.
_BUCKET_STREAM_TAG = 0x42C0DE


@partial(jax.jit, static_argnames=("chunk",))
def _bucket_chunk(k_bucket, c, cum, *, chunk: int):
    """Bucket index of each query in chunk ``c``: one uniform draw per
    query, inverted through the bucket CDF (``searchsorted`` over the
    cumulative probabilities, right-open intervals)."""
    kc = jax.random.fold_in(k_bucket, c)
    u = jax.random.uniform(kc, (chunk,), dtype=jnp.float32)
    return jnp.searchsorted(cum, u, side="right").astype(jnp.int32)


@dataclass(frozen=True)
class BucketedWorkloadSpec:
    """A :class:`WorkloadSpec` carrying a request-size rate matrix.

    ``rates[i][j]`` is the arrival rate (queries/s) of the bucket with
    input scale ``input_scales[i]`` and output scale ``output_scales[j]``
    — Mélange's 2D workload distribution.  The base spec's arrival and
    batch streams are untouched (``realize`` is bit-identical to
    ``base.realize`` on those fields); the bucket index of each query is
    drawn on device from its own ``fold_in``-derived stream, chunk for
    chunk, so a streaming consumer and ``realize`` see the same
    assignment.  Buckets flatten row-major into :class:`RequestBucket`
    descriptors whose scales multiply the analytical latency model
    (``instance.bucket_profile``).
    """

    base: WorkloadSpec
    rates: tuple[tuple[float, ...], ...]
    input_scales: tuple[float, ...] = (1.0,)
    output_scales: tuple[float, ...] = (1.0,)

    def __post_init__(self):
        r = len(self.rates)
        if r != len(self.input_scales):
            raise ValueError("rates must have one row per input scale")
        if any(len(row) != len(self.output_scales) for row in self.rates):
            raise ValueError("rates must have one column per output scale")
        flat = [float(v) for row in self.rates for v in row]
        if any(v < 0 for v in flat) or not sum(flat) > 0:
            raise ValueError("bucket rates must be >= 0 with a positive sum")
        if abs(sum(flat) - self.base.rate_qps) > 1e-6 * self.base.rate_qps:
            raise ValueError(
                f"bucket rates sum to {sum(flat):g} qps but the base spec "
                f"arrives at {self.base.rate_qps:g} qps")
        if any(not s > 0 for s in self.input_scales + self.output_scales):
            raise ValueError("bucket scales must be > 0")

    # Forwarded stream surface (streaming consumers use these).
    @property
    def seed(self) -> int:
        return self.base.seed

    @property
    def rate_qps(self) -> float:
        return self.base.rate_qps

    @property
    def effective_rate(self) -> float:
        return self.base.effective_rate

    @property
    def chunk(self) -> int:
        return self.base.chunk

    @property
    def scale(self) -> float:
        return self.base.scale

    @property
    def max_batch(self) -> int:
        return self.base.max_batch

    @property
    def n_buckets(self) -> int:
        return len(self.input_scales) * len(self.output_scales)

    @property
    def buckets(self) -> tuple[RequestBucket, ...]:
        """Row-major flattened bucket descriptors (the ``bucket_of`` index
        order)."""
        return tuple(
            RequestBucket(name=f"in{i}.out{j}", rate=float(self.rates[i][j]),
                          flops_scale=float(self.output_scales[j]),
                          bytes_scale=float(self.input_scales[i]))
            for i in range(len(self.input_scales))
            for j in range(len(self.output_scales)))

    def scaled(self, load_factor: float) -> "BucketedWorkloadSpec":
        """Heavier traffic, same bucket mix (all bucket rates scale by the
        factor, so the probabilities — and the drawn assignment — are
        unchanged)."""
        return replace(self, base=self.base.scaled(load_factor))

    def _bucket_key(self):
        return jax.random.fold_in(jax.random.PRNGKey(self.base.seed),
                                  _BUCKET_STREAM_TAG)

    def _cum_probs(self) -> np.ndarray:
        flat = np.asarray([v for row in self.rates for v in row],
                          dtype=np.float64)
        cum = np.cumsum(flat / flat.sum()).astype(np.float32)
        # The uniform draw lives in [0, 1); pin the last edge so f32
        # rounding can never push it below a drawn value (an out-of-range
        # bucket index).
        cum[-1] = 1.0
        return cum

    def generate_chunk(self, c: int, base: float):
        """Device arrays of chunk ``c``: (scaled arrivals f32, unscaled
        local arrivals f32, batches i32, bucket indices i32) — the first
        three bit-identical to ``self.base.generate_chunk(c, base)``."""
        arr, local, batches = self.base.generate_chunk(c, base)
        bucket = _bucket_chunk(self._bucket_key(), np.int64(c),
                               jnp.asarray(self._cum_probs()),
                               chunk=self.base.chunk)
        return arr, local, batches, bucket

    def realize(self, n_queries: int) -> Workload:
        """Host :class:`Workload` with per-query bucket indices; arrivals
        and batches are bit-identical to ``base.realize(n_queries)``."""
        if n_queries < 0:
            raise ValueError("n_queries must be >= 0")
        arrs: list[np.ndarray] = []
        bats: list[np.ndarray] = []
        bkts: list[np.ndarray] = []
        base = 0.0
        for c in range(math.ceil(n_queries / self.base.chunk)):
            _, local, batches, bucket = self.generate_chunk(c, base)
            local = np.asarray(jax.device_get(local))
            arrs.append(local)
            bats.append(np.asarray(jax.device_get(batches)))
            bkts.append(np.asarray(jax.device_get(bucket)))
            base = float(local[-1])
        arr64 = (np.concatenate(arrs)[:n_queries].astype(np.float64)
                 if arrs else np.zeros(0, dtype=np.float64))
        if self.base.scale != 1.0:
            arr64 = arr64 / np.float64(self.base.scale)
        bat64 = (np.concatenate(bats)[:n_queries].astype(np.int64)
                 if bats else np.zeros(0, dtype=np.int64))
        b64 = (np.concatenate(bkts)[:n_queries].astype(np.int64)
               if bkts else np.zeros(0, dtype=np.int64))
        return Workload(arrivals=arr64, batches=bat64,
                        rate_qps=float(self.base.effective_rate),
                        bucket_of=b64, buckets=self.buckets)


def generate_workload(seed: int, n_queries: int, rate_qps: float,
                      batch_dist: str = "lognormal",
                      median_batch: float = 24.0, sigma: float = 0.8,
                      mean_batch: float = 48.0, std_batch: float = 24.0,
                      max_batch: int = 256) -> Workload:
    """One seed, one stream: delegates to ``WorkloadSpec.realize`` so the
    legacy entrypoint and the chunked generator produce identical bits
    (the split-key whole-array draws this function used to make were a
    second, divergent PRNG stream for the same seed)."""
    spec = WorkloadSpec(seed=seed, rate_qps=rate_qps, batch_dist=batch_dist,
                        median_batch=median_batch, sigma=sigma,
                        mean_batch=mean_batch, std_batch=std_batch,
                        max_batch=max_batch)
    return spec.realize(n_queries)
