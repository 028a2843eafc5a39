"""Mean device time of one call of the jitted prefill step (trace)."""
from bench.measures import PREFILL, step_time


def read(run):
    m = step_time(run, PREFILL)
    return None if m is None else 1e3 * m[1] / m[0]
