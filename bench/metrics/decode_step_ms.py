"""Mean device time of one call of the jitted decode step (trace)."""
from bench.measures import DECODE, step_time


def read(run):
    m = step_time(run, DECODE)
    return None if m is None else 1e3 * m[1] / m[0]
