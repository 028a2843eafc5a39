"""Time per output token after the first, 90th percentile over every
request due in the window with more than one output token:
(done - first_token) / (out - 1)."""
from bench.measures import pct


def read(run):
    return pct([(r.done - r.first_token) / (r.req.out_len - 1)
                for r in run.due_in_window() if r.req.out_len > 1], 90)
