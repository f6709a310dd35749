"""Step builders: train (microbatched grad-accumulation + AdamW), prefill,
decode — the three lowering targets of the dry-run contract.

train_step handles the large-vocab memory wall by scanning over microbatches
(per-microbatch logits are the live peak; remat inside the model bounds layer
activations).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..models.transformer import ModelApi
from ..optim import adamw


def make_train_step(api: ModelApi, n_micro: int, lr: float = 3e-4,
                    param_dtype=None, grad_shardings=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch = {"tokens": (B,S), "labels": (B,S)[, "extra": (B,T,D)]}; the batch
    is split into n_micro microbatches along B, gradients accumulate in fp32.
    `grad_shardings` (a NamedSharding pytree matching params) pins the
    accumulated gradients to the parameter layout so FSDP weight-gradient
    reductions lower to reduce-scatter rather than all-reduce.
    """
    def train_step(params, opt_state, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        extra = batch.get("extra")
        b = tokens.shape[0]
        mb = b // n_micro
        mtok = tokens.reshape(n_micro, mb, *tokens.shape[1:])
        mlab = labels.reshape(n_micro, mb, *labels.shape[1:])
        mext = (extra.reshape(n_micro, mb, *extra.shape[1:])
                if extra is not None else None)

        def loss_of(p, tok, lab, ext):
            return api.loss(p, tok, lab, ext)

        def micro(acc, xs):
            if mext is None:
                tok, lab = xs
                ext = None
            else:
                tok, lab, ext = xs
            loss, g = jax.value_and_grad(loss_of)(params, tok, lab, ext)
            g32 = jax.tree.map(lambda a: a.astype(jnp.float32), g)
            if grad_shardings is not None:
                g32 = jax.tree.map(jax.lax.with_sharding_constraint, g32,
                                   grad_shardings)
            acc = jax.tree.map(jnp.add, acc, g32)
            return acc, loss

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        xs = (mtok, mlab) if mext is None else (mtok, mlab, mext)
        if n_micro == 1:
            grads, losses = micro(zeros, jax.tree.map(lambda a: a[0], xs))
            losses = losses[None]
        else:
            grads, losses = jax.lax.scan(micro, zeros, xs)
        grads = jax.tree.map(lambda g: g / n_micro, grads)

        gnorm = jnp.sqrt(sum(jnp.vdot(g, g)
                             for g in jax.tree.leaves(grads)).real)
        new_params, new_opt = adamw.update(grads, opt_state, lr=lr,
                                           param_dtype=param_dtype)
        return new_params, new_opt, {"loss": losses.mean(),
                                     "grad_norm": gnorm}

    return train_step


def make_prefill_step(api: ModelApi, max_len: int):
    def prefill_step(params, batch):
        if "lengths" in batch:     # decoder LMs: padded prompts of a length
            return api.prefill(params, batch["tokens"], max_len,
                               batch.get("extra"), lengths=batch["lengths"])
        return api.prefill(params, batch["tokens"], max_len,
                           batch.get("extra"))
    return prefill_step


def make_decode_step(api: ModelApi):
    def serve_step(params, cache, tokens):
        """One new token for every sequence against the standing cache,
        with the step's logits."""
        logits, cache = api.decode_step(params, cache, tokens)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache, logits
    return serve_step


# Decode steps whose logits the decode loop keeps (for a check against a
# reference).
KEPT_STEPS = 8


def make_decode_loop(api: ModelApi, max_steps: int):
    """Greedy decoding of ``n_steps`` (traced, at most ``max_steps``)
    tokens through the cache in one program, one ``make_decode_step`` a
    step.

    decode_loop(params, cache, logits, n_steps) -> {"fed": (B, max_steps)
    tokens fed at steps 1..n_steps (the first is the prefill logits'
    argmax), "at": (KEPT_STEPS,) the steps kept, 1-based: 1, then evenly
    spaced up to n_steps (repeats where n_steps < KEPT_STEPS), "kept":
    (KEPT_STEPS, B, V) float32 logits of those steps (a repeated step's
    logits in its first slot only), "active": the held experts that
    received a token, summed over steps and MoE layers (0 without expert
    shares)}.
    """
    step = make_decode_step(api)

    def decode_loop(params, cache, logits, n_steps):
        b, v = logits.shape[0], logits.shape[-1]
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        at = 1 + (jnp.arange(KEPT_STEPS) * (jnp.maximum(n_steps, 1) - 1)
                  // (KEPT_STEPS - 1))

        def body(i, carry):
            cache, tok, fed, kept, active = carry
            fed = fed.at[:, i].set(tok[:, 0])
            tok, cache, logits = step(params, cache, tok)
            hit = at == i + 1
            j = jnp.argmax(hit)
            kept = kept.at[j].set(jnp.where(
                hit[j], logits[:, -1].astype(jnp.float32), kept[j]))
            if "held_tokens" in cache:
                active = active + jnp.sum(cache["held_tokens"] > 0)
            return cache, tok, fed, kept, active

        carry = (cache, tok, jnp.zeros((b, max_steps), jnp.int32),
                 jnp.zeros((KEPT_STEPS, b, v), jnp.float32),
                 jnp.zeros((), jnp.int32))
        _, _, fed, kept, active = jax.lax.fori_loop(0, n_steps, body, carry)
        return {"fed": fed, "at": at, "kept": kept, "active": active}

    return decode_loop
