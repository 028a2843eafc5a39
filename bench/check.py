"""What decides ``correct``: the served tokens against the plain reference.

After the window a sample of the finished requests is drawn from the seed:
the longest request (prompt and answer) and then others in a seeded order
until ``served_tokens`` tokens are in it.  The reference of the
configuration's family runs once over each prompt followed by its served
tokens, and at every served position reads the gap by which the served
token's logit lies below the reference's best.  The widest gap is compared
with the cell's limit.  Greedy decoding serves the program's own argmax, so
a sound run only loses to the reference where two logits lie within the
program's rounding of each other.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.generator import rng_for

_SCORE_BYTES = 1 << 30       # float32 attention scores of one block


def sample(records: Sequence, seed: int, served_tokens: int) -> List:
    """The longest finished request, then others in a seeded order."""
    done = [r for r in records if r.tokens is not None]
    if not done:
        return []
    size = lambda r: len(r.req.prompt) + r.req.out_len
    longest = max(done, key=size)
    rest = [done[i] for i in rng_for(seed, 2).permutation(len(done))
            if done[i] is not longest]
    picked, total = [longest], longest.req.out_len
    for r in rest:
        if total >= served_tokens:
            break
        picked.append(r)
        total += r.req.out_len
    return picked


def teacher_rows(picked: Sequence, length: int, max_out: int, heads: int
                 ) -> Tuple[List[np.ndarray], List[np.ndarray],
                            List[np.ndarray], List[np.ndarray]]:
    """Blocks of (tokens, positions, targets, valid) for the reference.

    Row: the prompt, then the served tokens but the last, right-padded to
    ``length``.  Position j of a row is where served token j is predicted.
    """
    rows = max(1, _SCORE_BYTES // (heads * length * length * 4))
    out = ([], [], [], [])
    for i in range(0, len(picked), rows):
        blk = picked[i:i + rows]
        toks = np.zeros((len(blk), length), np.int32)
        pos = np.zeros((len(blk), max_out), np.int32)
        tgt = np.zeros((len(blk), max_out), np.int32)
        ok = np.zeros((len(blk), max_out), bool)
        for j, r in enumerate(blk):
            seq = np.concatenate([r.req.prompt, r.tokens[:-1]])
            toks[j, :len(seq)] = seq
            n, p = len(r.tokens), len(r.req.prompt)
            pos[j] = p - 1 + np.minimum(np.arange(max_out), n - 1)
            tgt[j, :n] = r.tokens
            ok[j, :n] = True
        for lst, a in zip(out, (toks, pos, tgt, ok)):
            lst.append(a)
    return out


@jax.jit
def _gaps(ref_logits, chosen, valid):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.where(valid, best - got, 0.0)


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def reference(family: str):
    return importlib.import_module(f"bench.reference.{family}")


def served_gaps(cell, seed: int, picked: Sequence,
                control: bool = False) -> Dict:
    """Widest gap of the served tokens; with ``control``, also of the
    tokens the fp8 reference puts first at the same positions."""
    ref, dims, dtype = (reference(cell.config["family"]), cell.dims,
                        cell.config["dtype"])
    toks, pos, tgt, ok = teacher_rows(picked, cell.max_len, cell.max_out,
                                      dims["heads"])
    exact = ref.logits_at(seed, dims, dtype, toks, pos, "f32")
    out = {"served": max(float(jnp.max(_gaps(l, jnp.asarray(t), jnp.asarray(v))))
                         for l, t, v in zip(exact, tgt, ok)),
           "tokens": int(sum(v.sum() for v in ok))}
    if control:
        low = ref.logits_at(seed, dims, dtype, toks, pos, "fp8")
        out["control"] = max(
            float(jnp.max(_gaps(l, _argmax(c), jnp.asarray(v))))
            for l, c, v in zip(exact, low, ok))
    return out


def checks(gap: float, served, limit: float) -> Dict:
    """The numbers that decide ``correct``, each beside its limit."""
    return {
        "max_logit_gap": {"value": gap, "limit": limit},
        "unanswered": {"value": served.unanswered, "limit": 0},
        "compiles_in_window": {"value": served.compiles, "limit": 0},
    }


def correct(numbers: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in numbers.values())
