"""Self device time of decode's ``kv_write`` scope per traced call (ms)."""
from bench import scopes


def read(run):
    return scopes.READERS["decode_kv_ms"](run)
