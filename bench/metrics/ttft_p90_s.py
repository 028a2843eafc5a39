"""Time to first token, 90th percentile over every request due in the
window: first_token - due, so a wait behind a busy server counts."""
from bench.measures import pct


def read(run):
    return pct([r.first_token - r.req.due for r in run.due_in_window()], 90)
