"""Serving steps: the decode_32k / long_500k cells lower these functions.

serve_step consumes one token per sequence and a state (KV cache for
attention families, O(1) recurrent state for SSM/hybrid), returning next
logits + updated state.  Sampling is greedy/temperature on top.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import api
from repro.models.config import ArchConfig


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


def temperature_sample(logits: jax.Array, key: jax.Array, temp: float = 0.8) -> jax.Array:
    return jax.random.categorical(key, logits[:, -1] / temp, axis=-1).astype(jnp.int32)[:, None]


def make_prefill_step(cfg: ArchConfig, ctx=None, *, with_logits: bool = False):
    """Prompt -> (next tokens, state); ``with_logits`` also returns the
    last-position logits [B,1,V] between them."""

    def prefill_step(params, batch, state):
        logits, state = api.prefill_fn(cfg, params, batch, state, ctx=ctx)
        if with_logits:
            return greedy_sample(logits), logits, state
        return greedy_sample(logits), state

    return prefill_step


def make_serve_step(cfg: ArchConfig, ctx=None, *, with_logits: bool = False):
    """One decode iteration: tokens [B,1] + state -> (next tokens, state);
    ``with_logits`` also returns the step's logits [B,1,V] between them."""

    def serve_step(params, tokens, state):
        logits, state = api.decode_fn(cfg, params, tokens, state, ctx=ctx)
        if with_logits:
            return greedy_sample(logits), logits, state
        return greedy_sample(logits), state

    return serve_step


def generate(cfg: ArchConfig, params, batch, max_new: int, ctx=None):
    """Prefill then decode max_new tokens (scan over serve_step)."""
    b, s = batch["tokens"].shape
    state = api.init_decode_state(cfg, b, s + max_new)
    logits, state = api.prefill_fn(cfg, params, batch, state, ctx=ctx)
    tok = greedy_sample(logits)
    serve = make_serve_step(cfg, ctx)

    def body(carry, _):
        tok, state = carry
        ntok, state = serve(params, tok, state)
        return (ntok, state), ntok[:, 0]

    (_, state), toks = jax.lax.scan(body, (tok, state), None, length=max_new - 1)
    out = jnp.concatenate([tok, toks.T], axis=1)
    return out, state
