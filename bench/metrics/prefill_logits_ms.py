"""Self device time of prefill's ``logits`` scope per traced call (ms)."""
from bench import scopes


def read(run):
    return scopes.READERS["prefill_logits_ms"](run)
