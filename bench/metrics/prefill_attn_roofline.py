"""Prefill attention against its bound (%): operations over the live
prompt tokens' causal pairs at peak, over ``attn_core`` self time."""
from bench import scopes


def read(run):
    return scopes.READERS["prefill_attn_roofline"](run)
