"""Seeded random weights, one layer at a time, for any family.

Every leaf is drawn from its own key, folded from the run's seed, the
layer's index and the leaf's place in the family's leaf list
(``LAYER_LEAVES`` then ``GLOBAL_LEAVES``), so a layer can be made again
alone: the program's weights are all layers made in one jitted call, and
the reference makes each layer again when it needs it.  ``fam`` is the
family's module (``bench/families/<family>.py``), which gives the leaves,
their shapes and their standard deviations; names are the benchmark's own,
and the family's ``program_params`` lays them out as the program expects.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_GLOBAL_INDEX = 1 << 20       # folded in place of a layer index


def seed_words(seed: int) -> np.ndarray:
    """Any whole number as two uint32 words, the jitted calls' argument."""
    s = seed % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _key(words, index, leaf: str, fam):
    k = jax.random.fold_in(jax.random.key(0), words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, index)
    return jax.random.fold_in(
        k, (fam.LAYER_LEAVES + fam.GLOBAL_LEAVES).index(leaf))


def _draw(words, index, leaf, shape, dims, dtype, fam):
    x = jax.random.normal(_key(words, index, leaf, fam), shape, jnp.float32)
    return (x * fam.std(leaf, dims)).astype(dtype)


def layer(words, index, dims: Dict, dtype, fam) -> Dict[str, jax.Array]:
    """One layer's leaves (traceable; ``index`` may be traced)."""
    return {n: _draw(words, index, n, s, dims, dtype, fam)
            for n, s in fam.layer_shapes(dims).items()}


def global_leaves(words, dims: Dict, dtype, fam) -> Dict[str, jax.Array]:
    return {n: _draw(words, _GLOBAL_INDEX, n, s, dims, dtype, fam)
            for n, s in fam.global_shapes(dims).items()}


def all_layers(words, dims: Dict, dtype, fam) -> Dict:
    """Every layer stacked on a leading axis, made one layer at a time so
    that no more than one layer's float32 draw is alive."""
    stacked = jax.lax.map(lambda i: layer(words, i, dims, dtype, fam),
                          jnp.arange(dims["layers"]))
    return {"layers": stacked, **global_leaves(words, dims, dtype, fam)}
