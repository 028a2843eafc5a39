"""Seeded random weights of a dense decoder, one layer at a time.

Every leaf is drawn from its own key, folded from the run's seed, the
layer's index and the leaf's name, so a layer can be made again alone:
the program's weights are all layers made in one jitted call, and the
reference makes each layer again when it needs it.  Names here are the
benchmark's own; ``adapter.program_params`` lays them out as the program
expects.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo",
                "mlp_norm", "w_up", "w_gate", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm")
_GLOBAL_INDEX = 1 << 20       # folded in place of a layer index


def layer_shapes(dims: Dict) -> Dict[str, tuple]:
    d, h, k, hd, ff = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    return {"attn_norm": (d,), "wq": (d, h, hd), "wk": (d, k, hd),
            "wv": (d, k, hd), "wo": (h, hd, d), "mlp_norm": (d,),
            "w_up": (d, ff), "w_gate": (d, ff), "w_down": (ff, d)}


def _std(name: str, dims: Dict) -> float:
    if name in ("attn_norm", "mlp_norm", "final_norm"):
        return 0.1            # norms multiply by (1 + w)
    if name == "embed":
        return 0.02
    fan_in = {"wo": dims["heads"] * dims["head_dim"],
              "w_down": dims["ff"]}.get(name, dims["d"])
    return 1.0 / math.sqrt(fan_in)


def seed_words(seed: int) -> np.ndarray:
    """Any whole number as two uint32 words, the jitted calls' argument."""
    s = seed % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _key(words, index, leaf: str):
    k = jax.random.fold_in(jax.random.key(0), words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, index)
    return jax.random.fold_in(k, (LAYER_LEAVES + GLOBAL_LEAVES).index(leaf))


def _draw(words, index, leaf, shape, dims, dtype):
    x = jax.random.normal(_key(words, index, leaf), shape, jnp.float32)
    return (x * _std(leaf, dims)).astype(dtype)


def layer(words, index, dims: Dict, dtype) -> Dict[str, jax.Array]:
    """One layer's leaves (traceable; ``index`` may be traced)."""
    return {n: _draw(words, index, n, s, dims, dtype)
            for n, s in layer_shapes(dims).items()}


def global_leaves(words, dims: Dict, dtype) -> Dict[str, jax.Array]:
    shapes = {"embed": (dims["vocab"], dims["d"]), "final_norm": (dims["d"],)}
    return {n: _draw(words, _GLOBAL_INDEX, n, s, dims, dtype)
            for n, s in shapes.items()}


def all_layers(words, dims: Dict, dtype) -> Dict:
    """Every layer stacked on a leading axis, made one layer at a time so
    that no more than one layer's float32 draw is alive."""
    stacked = jax.lax.map(lambda i: layer(words, i, dims, dtype),
                          jnp.arange(dims["layers"]))
    return {"layers": stacked, **global_leaves(words, dims, dtype)}
