"""Process start to the window's start: imports, device, weights made from
the seed, and every shape of the cell compiled or loaded and run once."""


def read(run):
    return run.setup_s
