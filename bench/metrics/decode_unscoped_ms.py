"""Self device time of decode's ops under no known scope per traced call
(ms): the layer scan's slicing and stacking, copies XLA inserts."""
from bench import scopes


def read(run):
    return scopes.READERS["decode_unscoped_ms"](run)
