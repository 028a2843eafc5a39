"""Self device time of prefill's ``moe`` scope per traced call (ms)."""
from bench import scopes
from bench.measures import PREFILL


def read(run):
    return scopes.ms_per_call(run, PREFILL, "moe")
