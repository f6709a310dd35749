"""Live LLM execution for ``ClusterEngine``: one query is a static batch
of requests that share a prompt length and an output length.

A query runs one prefill of its batch at its prompt bin, then greedy
decoding through the cache for its output length, in one program
(``launch.steps.make_decode_loop``).  Executables are bucketed by batch
(powers of two) and prompt bin; ``warmup`` compiles all of them.  One
parameter copy (bfloat16, float32 router) serves every cell type.

The programs are the modules ``jit_live_prefill`` and ``jit_live_decode``
of a device trace.  Program spans (``repro.tracing``): ``live.prefill`` and ``live.decode``
each cover their program's dispatch and its completion on the device,
with the batch bucket (``batch``), the prompt bin (``prompt_bin``), the
real requests (``rows``), the real prompt length (``prompt``) and, for
decoding, the steps (``steps``).  While a session records, each also
carries the step's program counter read back afterwards: the tokens the
held experts received in the prefill (``held_tokens``), and the held
experts that received a token, summed over decode steps and MoE layers
(``active``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..configs.base import ArchConfig
from ..launch.steps import make_decode_loop, make_prefill_step
from ..models.transformer import get_model


@dataclass(frozen=True)
class LLMQuery:
    """One query: ``rows`` requests of ``prompt`` tokens each, decoding
    ``steps`` tokens; ``index`` (its place in the stream) keys its prompt tokens."""

    rows: int
    prompt: int
    steps: int
    index: int


def pow2_bucket(n: int) -> int:
    return 1 << int(np.ceil(np.log2(max(int(n), 1))))


class LLMExecutor:
    """Prefill and decode executables of one architecture."""

    def __init__(self, cfg: ArchConfig, seed: int = 0,
                 prompt_bins=(512, 2048), max_output: int = 256,
                 max_batch: int = 32):
        self.cfg = cfg
        self.api = get_model(cfg)
        self.bins = tuple(sorted(int(b) for b in prompt_bins))
        self.max_output = int(max_output)
        self.max_batch = int(max_batch)
        self.key = jax.random.PRNGKey(seed)
        self.params = jax.jit(self.api.init_params, static_argnums=1)(
            self.key, jnp.bfloat16)
        self._prefill, self._decode = {}, {}
        self.token_key = jax.random.fold_in(self.key, 0x70CE)

    def bin_of(self, prompt: int) -> int:
        for b in self.bins:
            if prompt <= b:
                return b
        raise ValueError(f"prompt of {prompt} tokens exceeds the largest "
                         f"bin {self.bins[-1]}")

    def buckets(self):
        b, out = 1, []
        while b <= self.max_batch:
            out.append(b)
            b *= 2
        return out

    # -- programs ------------------------------------------------------
    def _prefill_fn(self, bucket: int, pbin: int):
        step = make_prefill_step(self.api, max_len=pbin + self.max_output)
        vocab = self.cfg.vocab_size

        # the token key is an argument, not a constant: the program (and
        # its persistent-cache entry) is the same for every seed
        def live_prefill(params, token_key, index, prompt, rows):
            key = jax.random.fold_in(token_key, index)
            tokens = jax.random.randint(key, (bucket, pbin), 0, vocab,
                                        dtype=jnp.int32)
            lengths = jnp.where(jnp.arange(bucket) < rows, prompt, 0)
            cache, logits = step(params, {"tokens": tokens,
                                          "lengths": lengths})
            return tokens, cache, logits

        return jax.jit(live_prefill)

    def _decode_fn(self):
        loop = make_decode_loop(self.api, self.max_output)

        def live_decode(params, cache, logits, n_steps):
            return loop(params, cache, logits, n_steps)

        return jax.jit(live_decode)

    def warmup(self, max_batch: int | None = None) -> None:
        """Compiles every (batch bucket x prompt bin) prefill and decode
        program, in parallel, then runs each once."""
        buckets = [b for b in self.buckets()
                   if max_batch is None or b <= max_batch]
        shapes = [(b, p) for b in buckets for p in self.bins]
        key = jax.ShapeDtypeStruct(self.token_key.shape, self.token_key.dtype)
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        p_abs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             self.params)

        def compile_pair(shape):
            b, p = shape
            pre = self._prefill_fn(b, p).lower(p_abs, key, u32, i32, i32)
            _, cache, logits = jax.eval_shape(self._prefill_fn(b, p), p_abs,
                                              key, u32, i32, i32)
            dec = self._decode_fn().lower(p_abs, cache, logits, i32)
            return shape, pre.compile(), dec.compile()

        with ThreadPoolExecutor(max_workers=min(8, len(shapes))) as pool:
            for shape, pre, dec in pool.map(compile_pair, shapes):
                self._prefill[shape], self._decode[shape] = pre, dec
        for b, p in shapes:
            self.run(self.params, LLMQuery(b, min(p, 16), 1, 0xFFFFFFFF))

    # -- one query -----------------------------------------------------
    def run(self, params, q: LLMQuery) -> dict:
        """Prefill and decode one query; returns its outputs (device
        arrays): the prompt tokens (``tokens``), the prefill's last logits
        (``prefill``, (B, 1, V)) and the decode loop's result."""
        bucket, pbin = pow2_bucket(q.rows), self.bin_of(q.prompt)
        shape = (bucket, pbin)
        if shape not in self._prefill:
            self._prefill[shape] = self._prefill_fn(*shape)
            self._decode[shape] = self._decode_fn()
        common = dict(batch=bucket, prompt_bin=pbin, rows=q.rows,
                      prompt=q.prompt)
        with tracing.span("live.prefill", **common) as sp:
            tokens, cache, logits = self._prefill[shape](
                params, self.token_key, np.uint32(q.index),
                np.int32(q.prompt), np.int32(q.rows))
            jax.block_until_ready(logits)
            if sp is not None and "held_tokens" in cache:
                sp.args["held_tokens"] = int(np.sum(jax.device_get(
                    cache["held_tokens"])))
        with tracing.span("live.decode", steps=q.steps, **common) as sp:
            out = self._decode[shape](params, cache, logits,
                                      np.int32(q.steps))
            jax.block_until_ready(out)
            if sp is not None:
                sp.args["active"] = int(out["active"])
        return dict(out, tokens=tokens, prefill=logits)
