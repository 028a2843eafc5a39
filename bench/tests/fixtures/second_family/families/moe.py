"""A top-k mixture-of-experts decoder, as a family the harness can take.

The attention is the dense family's; each layer's SwiGLU MLP is replaced
by ``experts`` SwiGLU experts of width ``ff`` behind a softmax router that
sends each token to its ``topk`` best, weighted by their renormalized
gates.  The program drops a token an expert has no capacity left for; a
configuration whose capacity factor is ``experts / topk`` drops none.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

from bench.costs import BF16
from bench.families import dense
from bench.families.dense import (GLOBAL_LEAVES, decode_attn_counts,  # noqa: F401
                                  global_shapes, kv_bytes_per_token,
                                  prefill_attn_flops)

CATALOG_KEYS = {**dense.CATALOG_KEYS,
                "num_local_experts": "num_experts",
                "num_experts_per_tok": "experts_per_token",
                "capacity_factor": "moe_capacity_factor"}

_MLP = ("w_up", "w_gate", "w_down")
LAYER_LEAVES = tuple(n for n in dense.LAYER_LEAVES if n not in _MLP) + (
    "router", "e_up", "e_gate", "e_down")

SCOPES = ("attn_qkv", "kv_write", "attn_core", "attn_out", "moe")


def dims(config: Dict) -> Dict:
    return {**dense.dims(config), "experts": config["num_local_experts"],
            "topk": config["num_experts_per_tok"]}


def layer_shapes(dims: Dict) -> Dict[str, tuple]:
    d, ff, e = dims["d"], dims["ff"], dims["experts"]
    shapes = {n: s for n, s in dense.layer_shapes(dims).items()
              if n not in _MLP}
    return {**shapes, "router": (d, e), "e_up": (e, d, ff),
            "e_gate": (e, d, ff), "e_down": (e, ff, d)}


def std(name: str, dims: Dict) -> float:
    if name == "e_down":
        return 1.0 / math.sqrt(dims["ff"])
    return dense.std(name, dims)


def program_params(w: Dict) -> Dict:
    lw = w["layers"]
    tree = dense.program_params({**w, "layers": {
        **lw, "w_up": lw["e_up"], "w_gate": lw["e_gate"],
        "w_down": lw["e_down"]}})
    tree["layers"]["ffn"]["router"] = lw["router"]
    return tree


def _attn_params(dims: Dict) -> int:
    """Attention and both norms of a layer: the dense layer without its MLP."""
    return dense.layer_params({**dims, "ff": 0})


def _active_params(dims: Dict) -> int:
    """Per token: attention, norms, the router and ``topk`` experts."""
    expert = 3 * dims["d"] * dims["ff"]
    return dims["layers"] * (_attn_params(dims) + dims["d"] * dims["experts"]
                             + dims["topk"] * expert) + dims["d"]


def _all_params(dims: Dict) -> int:
    expert = 3 * dims["d"] * dims["ff"]
    return (dims["layers"] * (_attn_params(dims)
                              + dims["experts"] * (dims["d"] + expert))
            + dims["d"] + dims["vocab"] * dims["d"])


def _head_flops(dims: Dict) -> float:
    return 2.0 * dims["d"] * dims["vocab"]


def prefill_flops(dims: Dict, lengths: Sequence[int]) -> float:
    return (sum(2.0 * _active_params(dims) * n + _head_flops(dims)
                for n in lengths) + prefill_attn_flops(dims, lengths))


def prefill_bytes(dims: Dict, lengths: Sequence[int]) -> float:
    return (_all_params(dims) * BF16
            + sum(lengths) * kv_bytes_per_token(dims))


def decode_flops(dims: Dict, contexts: Sequence[int]) -> float:
    return (sum(2.0 * _active_params(dims) + _head_flops(dims)
                for _ in contexts) + decode_attn_counts(dims, contexts)[0])


def decode_bytes(dims: Dict, contexts: Sequence[int]) -> float:
    return (_all_params(dims) * BF16
            + sum(contexts) * kv_bytes_per_token(dims))
