"""Median wait in the batcher's queue: dispatch - due, over every request
due in the window."""
from bench.measures import pct


def read(run):
    return pct([r.dispatch - r.req.due for r in run.due_in_window()], 50)
