"""Model operations of every traced prefill and decode step of a chat cell
over their device time at the chip's bf16 peak (%)."""
from bench.measures import step_mfu


def read(run):
    return step_mfu(run)
