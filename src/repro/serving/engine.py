"""Inference engine: jitted prefill / decode steps over any model family.

``make_prefill_fn`` builds the cache *inside* the jit (so the dry-run does
not need a cache operand) and returns (cache, last-token logits);
``make_decode_fn`` is the one-token step with the cache donated so XLA
aliases it in place — the KV cache is read-modify-write, never copied.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.registry import build_model


def serving_config(cfg: ModelConfig) -> ModelConfig:
    """Inference variant: bf16 params (or the config's serve dtype)."""
    return dataclasses.replace(cfg, param_dtype=cfg.serve_param_dtype)


def make_prefill_fn(model, max_len: Optional[int] = None) -> Callable:
    cfg = model.cfg

    def prefill_step(params, tokens, lengths, frames=None, patches=None):
        B, S = tokens.shape
        total = S + (patches.shape[1] if patches is not None else 0)
        cache_len = max_len or total
        kwargs: Dict[str, Any] = {}
        if cfg.is_encdec:
            cache = model.init_cache(B, cache_len, enc_len=frames.shape[1])
            kwargs["frames"] = frames
        else:
            cache = model.init_cache(B, cache_len)
            if patches is not None:
                kwargs["prefix_embeds"] = patches
        return model.prefill(params, cache, tokens, lengths, **kwargs)

    return prefill_step


def make_decode_fn(model) -> Callable:
    def decode_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    return decode_step


def cached_and_full_logits(model, params, tokens: jnp.ndarray,
                           prompt_len: int, max_len: int):
    """Next-token logits at positions ``prompt_len-1 .. S-1``, two ways.

    ``cached``: prefill of the first ``prompt_len`` tokens, then one decode
    step per remaining token through the cache.  ``full``: one
    teacher-forced ``forward`` over all S tokens.  Both (B, S-prompt_len+1,
    V); they agree up to rounding when the cache path is right.
    """
    B, S = tokens.shape
    prefill = jax.jit(make_prefill_fn(model, max_len=max_len))
    decode = jax.jit(make_decode_fn(model), donate_argnums=(1,))
    forward = jax.jit(lambda p, t: model.forward(p, t)[0])
    cache, logits = prefill(params, tokens[:, :prompt_len],
                            jnp.full((B,), prompt_len, jnp.int32))
    cached = [logits]
    for t in range(prompt_len, S):
        cache, logits = decode(params, cache, tokens[:, t])
        cached.append(logits)
    full = forward(params, tokens)[:, prompt_len - 1:]
    return jnp.stack(cached, axis=1), full


def greedy_sample(logits: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def make_generate_fn(model, steps: int) -> Callable:
    """prefill + `steps` greedy decode steps, scanned (for smoke/e2e tests)."""
    prefill = make_prefill_fn(model)
    decode = make_decode_fn(model)

    def generate(params, tokens, lengths, **kw):
        cache, logits = prefill(params, tokens, lengths, **kw)
        nxt = greedy_sample(logits)

        def body(carry, _):
            cache, tok = carry
            cache, logits = decode(params, cache, tok)
            nxt = greedy_sample(logits)
            return (cache, nxt), nxt

        (cache, _), toks = jax.lax.scan(body, (cache, nxt), None, length=steps)
        return jnp.concatenate([nxt[:, None], toks.T], axis=1)

    return generate
