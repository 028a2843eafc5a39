"""Plain forward pass of a dense GQA decoder, the yardstick for ``correct``.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no KV
cache, no batching, no padding of its own, no kernels.  Causal attention
over the whole sequence, so the right-padding of a block of sequences
cannot reach the positions that are read.  It imports nothing of the
program: its weights come from ``bench.weights`` and the seed, made again
one layer at a time, so the reference fits beside nothing else on the chip.

The architecture, with the program's departures from granite (noted in the
configurations' ``assumed``): token embedding times sqrt(d); per layer
``x += Attn(RMSNorm(x))``, ``x += SwiGLU(RMSNorm(x))`` with
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; rotary embedding
on the two halves of each head (theta 10000); query head h reads key/value
head ``h // (heads / kv_heads)``; a final RMSNorm and the tied embedding as
the output head.

``prec="fp8"`` is the control: every matrix product takes both operands
rounded to float8 e4m3, each scaled by its largest magnitude, and
accumulates in float32.  That is one step below the bf16 the configuration
states; the check has to tell it from the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.families import dense as F

EPS = 1e-6
ROPE_THETA = 10_000.0
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _round_fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _F8_MAX
    return (a / scale).astype(_F8).astype(jnp.float32) * scale


def _mm(spec: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (1.0 + w)


def _rope(x, positions):
    """x: (R, S, n, hd); rotates the first half against the second."""
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer_weights(words, index, dims, dtype):
    """A layer as served (in ``dtype``), held in float32."""
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        W.layer(words, index, dict(dims), jnp.dtype(dtype), F))


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _global_weights(words, dims, dtype):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        W.global_leaves(words, dict(dims), jnp.dtype(dtype),
                                        F))


@jax.jit
def _embed(g, tokens):
    return g["embed"][tokens] * math.sqrt(g["embed"].shape[1])


@functools.partial(jax.jit, static_argnames=("prec",))
def _layer(x, w, prec: str):
    R, S, _ = x.shape
    H, hd = w["wq"].shape[1], w["wq"].shape[2]
    K = w["wk"].shape[1]
    pos = jnp.broadcast_to(jnp.arange(S), (R, S))
    h = _rmsnorm(x, w["attn_norm"])
    q = _rope(_mm("rsd,dhk->rshk", h, w["wq"], prec), pos)
    k = _rope(_mm("rsd,dhk->rshk", h, w["wk"], prec), pos)
    v = _mm("rsd,dhk->rshk", h, w["wv"], prec)
    q = q.reshape(R, S, K, H // K, hd)
    s = _mm("rqkgd,rtkd->rkgqt", q, k, prec) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("rkgqt,rtkd->rqkgd", p, v, prec).reshape(R, S, H, hd)
    x = x + _mm("rshk,hkd->rsd", o, w["wo"], prec)
    h = _rmsnorm(x, w["mlp_norm"])
    up = _mm("rsd,df->rsf", h, w["w_up"], prec)
    gate = _mm("rsd,df->rsf", h, w["w_gate"], prec)
    return x + _mm("rsf,fd->rsd", jax.nn.silu(gate) * up, w["w_down"], prec)


@functools.partial(jax.jit, static_argnames=("prec",))
def _head(x, g, positions, prec: str):
    """Logits (R, P, V) at the given positions of each row."""
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    xs = _rmsnorm(xs, g["final_norm"])
    return _mm("rpd,vd->rpv", xs, g["embed"], prec)


def logits_at(seed: int, dims: Dict, dtype: str,
              blocks: Sequence[np.ndarray], positions: Sequence[np.ndarray],
              prec: str = "f32") -> List[jax.Array]:
    """Next-token logits of each block of token rows at ``positions``.

    ``dtype`` is the type the weights are served in; the reference holds
    those values in float32.  ``blocks[i]``: (R, S) int32 token rows;
    ``positions[i]``: (R, P) int32.  Layer by layer over all blocks, so
    each layer's weights are made once.
    """
    words = W.seed_words(seed)
    key = tuple(sorted(dims.items()))
    g = _global_weights(words, key, dtype)
    xs = [_embed(g, jnp.asarray(b)) for b in blocks]
    for i in range(dims["layers"]):
        w = _layer_weights(words, np.int32(i), key, dtype)
        xs = [_layer(x, w, prec) for x in xs]
        del w
    return [_head(x, g, jnp.asarray(p), prec) for x, p in zip(xs, positions)]
