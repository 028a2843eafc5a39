"""Plain float32 forward pass of the mixture-of-experts family.

The dense reference's embedding, norms, rotary attention and head
(``bench.reference.dense``), with each layer's MLP replaced by the experts:
a softmax router in float32, each token's ``topk`` best experts weighted
by their gates renormalized to sum to 1, and no token dropped.  Weights
from ``bench.weights`` and the seed, one layer at a time.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.families import moe as F
from bench.reference.dense import _embed, _head, _mm, _rmsnorm, _rope


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer_weights(words, index, dims, dtype):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        W.layer(words, index, dict(dims), jnp.dtype(dtype), F))


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _global_weights(words, dims, dtype):
    return jax.tree.map(lambda a: a.astype(jnp.float32), W.global_leaves(
        words, dict(dims), jnp.dtype(dtype), F))


@functools.partial(jax.jit, static_argnames=("topk", "prec"))
def _layer(x, w, topk: int, prec: str):
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
    gates = jax.nn.softmax(_mm("rsd,de->rse", h, w["router"], prec), axis=-1)
    top, idx = jax.lax.top_k(gates, topk)
    top = top / top.sum(-1, keepdims=True)
    mix = jnp.sum(jax.nn.one_hot(idx, gates.shape[-1]) * top[..., None], -2)
    up = _mm("rsd,edf->rsef", h, w["e_up"], prec)
    gate = _mm("rsd,edf->rsef", h, w["e_gate"], prec)
    out = _mm("rsef,efd->rsed", jax.nn.silu(gate) * up, w["e_down"], prec)
    return x + jnp.einsum("rse,rsed->rsd", mix, out,
                          precision=jax.lax.Precision.HIGHEST)


def logits_at(seed: int, dims: Dict, dtype: str,
              blocks: Sequence[np.ndarray], positions: Sequence[np.ndarray],
              prec: str = "f32") -> List[jax.Array]:
    """Next-token logits of each block of token rows at ``positions``."""
    words = W.seed_words(seed)
    key = tuple(sorted(dims.items()))
    g = _global_weights(words, key, dtype)
    xs = [_embed(g, jnp.asarray(b)) for b in blocks]
    for i in range(dims["layers"]):
        w = _layer_weights(words, np.int32(i), key, dtype)
        xs = [_layer(x, w, dims["topk"], prec) for x in xs]
    return [_head(x, g, jnp.asarray(p), prec) for x, p in zip(xs, positions)]
