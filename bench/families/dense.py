"""The dense GQA decoder: what the harness knows of its block.

Per layer ``x += Attn(RMSNorm(x))`` with grouped-query attention, then
``x += SwiGLU(RMSNorm(x))``; a token embedding, a final norm and the tied
embedding as the output head.  This module gives the configuration file's
keys, the sizes the harness works with, the seeded leaves and their layout
in the program's tree, the operations and bytes of the served steps, and
the scopes the program names inside this block.

Counts take only the work that a request needs (``bench.costs``): the live
(unpadded) prompt tokens, the cache entries a row has written, the output
head at the one position a step samples from, and rows still owed a token.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

from bench.costs import BF16

# a configuration file's keys and the program's ModelConfig fields
CATALOG_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
                "num_attention_heads": "num_heads",
                "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
                "intermediate_size": "d_ff", "vocab_size": "vocab_size"}

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo",
                "mlp_norm", "w_up", "w_gate", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm")

# the program's jax.named_scope names inside this family's layers
SCOPES = ("attn_qkv", "kv_write", "attn_core", "attn_out", "mlp")


def dims(config: Dict) -> Dict:
    c = config
    return {"layers": c["num_hidden_layers"], "d": c["hidden_size"],
            "heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "ff": c["intermediate_size"],
            "vocab": c["vocab_size"]}


# ---------------------------------------------------------------- weights
def layer_shapes(dims: Dict) -> Dict[str, tuple]:
    d, h, k, hd, ff = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    return {"attn_norm": (d,), "wq": (d, h, hd), "wk": (d, k, hd),
            "wv": (d, k, hd), "wo": (h, hd, d), "mlp_norm": (d,),
            "w_up": (d, ff), "w_gate": (d, ff), "w_down": (ff, d)}


def global_shapes(dims: Dict) -> Dict[str, tuple]:
    return {"embed": (dims["vocab"], dims["d"]), "final_norm": (dims["d"],)}


def std(name: str, dims: Dict) -> float:
    if name in ("attn_norm", "mlp_norm", "final_norm"):
        return 0.1            # norms multiply by (1 + w)
    if name == "embed":
        return 0.02
    fan_in = {"wo": dims["heads"] * dims["head_dim"],
              "w_down": dims["ff"]}.get(name, dims["d"])
    return 1.0 / math.sqrt(fan_in)


def program_params(w: Dict) -> Dict:
    """The benchmark's leaf names laid out as the program's tree."""
    lw = w["layers"]
    return {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": lw["attn_norm"]},
            "ln2": {"scale": lw["mlp_norm"]},
            "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo")},
            "ffn": {"wi": lw["w_up"], "wg": lw["w_gate"],
                    "wo": lw["w_down"]},
        },
    }


# ----------------------------------------------------------------- counts
def layer_params(dims: Dict) -> int:
    d, h, k, hd, ff = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    return 2 * d * h * hd + 2 * d * k * hd + 3 * d * ff + 2 * d


def nonembed_params(dims: Dict) -> int:
    return dims["layers"] * layer_params(dims) + dims["d"]


def all_params(dims: Dict) -> int:
    return nonembed_params(dims) + dims["vocab"] * dims["d"]


def kv_bytes_per_token(dims: Dict) -> int:
    return dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"] * BF16


def _attn_flops(dims: Dict, pairs: float) -> float:
    """Scores and weighted values over ``pairs`` (query, key) pairs."""
    return 4.0 * dims["layers"] * dims["heads"] * dims["head_dim"] * pairs


def _head_flops(dims: Dict) -> float:
    return 2.0 * dims["d"] * dims["vocab"]


def prefill_flops(dims: Dict, lengths: Sequence[int]) -> float:
    """Causal prefill of each prompt, head at its last position only."""
    return sum(2.0 * nonembed_params(dims) * n
               + _attn_flops(dims, n * (n + 1) / 2) + _head_flops(dims)
               for n in lengths)


def prefill_bytes(dims: Dict, lengths: Sequence[int]) -> float:
    """Weights read once, the prompts' keys and values written once."""
    return (all_params(dims) * BF16
            + sum(lengths) * kv_bytes_per_token(dims))


def decode_flops(dims: Dict, contexts: Sequence[int]) -> float:
    """One token for each row still owed one; ``contexts`` are the cache
    entries each such row attends over, its new token's included."""
    return sum(2.0 * nonembed_params(dims) + _attn_flops(dims, c)
               + _head_flops(dims) for c in contexts)


def decode_bytes(dims: Dict, contexts: Sequence[int]) -> float:
    """All weights (the head is the tied embedding), plus each such row's
    live cache entries."""
    return (all_params(dims) * BF16
            + sum(contexts) * kv_bytes_per_token(dims))


def decode_attn_counts(dims: Dict, contexts: Sequence[int]
                       ) -> Tuple[float, float]:
    """(operations, bytes) of one decode call's attention: scores and
    weighted values over the live entries of each row still owed a token,
    and those entries' keys and values read once."""
    live = sum(contexts)
    return _attn_flops(dims, live), live * kv_bytes_per_token(dims)


def prefill_attn_flops(dims: Dict, lengths: Sequence[int]) -> float:
    """Causal attention over each prompt's live (query, key) pairs."""
    return _attn_flops(dims, sum(n * (n + 1) / 2 for n in lengths))
