"""Self device time of decode's ``attn_core`` scope per traced call (ms)."""
from bench import scopes


def read(run):
    return scopes.READERS["decode_attn_ms"](run)
