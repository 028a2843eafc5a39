"""The least time the chip could take over a served step's work.

Each family (``bench/families/<family>.py``) counts the operations and
bytes its served steps need, from shapes.  Only the work that a request
needs is counted: the live (unpadded) prompt tokens, the cache entries a
row has written, the output head at the one position a step samples from,
and rows still owed a token.  Work the program spends on padding, empty
cache slots, finished rows or logits at every prompt position is left out,
so removing it raises a roofline share.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def bound_s(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
