"""Operations and bytes the served steps need, computed from shapes.

Only the work that a request needs is counted: the live (unpadded) prompt
tokens, the cache entries a row has written, the output head at the one
position a step samples from, and rows still owed a token.  Work the
program spends on padding, empty cache slots, finished rows or logits at
every prompt position is left out, so removing it raises a roofline share.
"""
from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


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


def bound_s(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
