"""Decode step against its bound (%): all bf16 weights plus the live cache
entries of the rows still owed a token, at peak bandwidth (or their
operations at peak, whichever is longer), over the traced device time."""
from bench.measures import DECODE, roofline


def read(run):
    return roofline(run, DECODE)
