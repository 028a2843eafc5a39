"""Decode attention against its bound (%): operations over the live
(query, key) pairs at peak, or the live entries' bytes at peak bandwidth,
whichever is longer, over ``attn_core`` self time."""
from bench import scopes


def read(run):
    return scopes.READERS["decode_attn_roofline"](run)
