"""Self device time of prefill's ``attn_core`` scope per traced call (ms)."""
from bench import scopes


def read(run):
    return scopes.READERS["prefill_attn_ms"](run)
