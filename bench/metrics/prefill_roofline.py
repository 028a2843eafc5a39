"""Prefill against its bound (%): the operations of the live prompt tokens
(2 x non-embedding parameters per token, causal attention over live pairs,
the head at the last position only) at peak, or the bytes at peak
bandwidth, whichever is longer, over the traced device time."""
from bench.measures import PREFILL, roofline


def read(run):
    return roofline(run, PREFILL)
