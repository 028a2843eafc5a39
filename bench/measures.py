"""What the metric readers share: a run's records, and the work of the
traced steps counted from their shapes by the cell's family
(``bench/families/<family>.py``) and bounded by ``bench.costs``."""
from __future__ import annotations

import dataclasses
import sys
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from bench import costs
from bench import trace_reduce

PREFILL = "jit_serve_prefill"
DECODE = "jit_serve_decode"


@dataclasses.dataclass
class Run:
    family: ModuleType      # bench/families/<family>.py: the counts
    dims: Dict
    seconds: float          # the measured window
    setup_s: float
    records: List           # adapter.Record, every request sent
    batches: List           # adapter.Batch
    peaks: Dict
    trace: Optional[Dict] = None    # scopes.summarize(...)

    def due_in_window(self) -> List:
        return [r for r in self.records if r.req.due < self.seconds]

    def done_in_window(self) -> List:
        return [r for r in self.records
                if r.tokens is not None and r.done <= self.seconds]


def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def decode_contexts(run: Run) -> List[List[int]]:
    """Live cache entries of each row still owed a token, per traced decode
    call, in order: step j of a batch serves the rows owed a (j + 2)th
    token, over their prompt and the j + 1 tokens after it."""
    out = []
    for b in run.batches:
        if b.traced:
            out += [[n + j + 1 for n, o in zip(b.lengths, b.outs) if j + 2 <= o]
                    for j in range(max(b.outs) - 1)]
    return out


def _calls(run: Run, kind: str) -> List[tuple]:
    """(flops, bytes) of each traced call of one step, in order."""
    fam, dims = run.family, run.dims
    if kind == PREFILL:
        return [(fam.prefill_flops(dims, b.lengths),
                 fam.prefill_bytes(dims, b.lengths))
                for b in run.batches if b.traced]
    return [(fam.decode_flops(dims, c), fam.decode_bytes(dims, c))
            for c in decode_contexts(run)]


def step_time(run: Run, kind: str) -> Optional[tuple]:
    """(calls, device seconds) of a step, where the trace holds exactly the
    calls the harness made while tracing; None, said on stderr, otherwise."""
    if run.trace is None:
        return None
    m = trace_reduce.module(run.trace, kind)
    made = len(_calls(run, kind))
    if m is None or m[0] != made or m[1] <= 0:
        if made or m is not None:
            print(f"trace: {kind} has {m} (calls, s) in the trace, the "
                  f"harness made {made} calls while tracing; left out",
                  file=sys.stderr)
        return None
    return m


def roofline(run: Run, kind: str) -> Optional[float]:
    """Least time the chip could take over the traced calls, as a share
    (%) of their device time."""
    m = step_time(run, kind)
    if m is None:
        return None
    bound = sum(costs.bound_s(f, b, run.peaks) for f, b in _calls(run, kind))
    return 100.0 * bound / m[1]


def step_mfu(run: Run) -> Optional[float]:
    """Model operations of every traced step over their device time at the
    chip's peak (%)."""
    flops = seconds = 0.0
    for kind in (PREFILL, DECODE):
        m = step_time(run, kind)
        if m is None:
            if _calls(run, kind):
                return None
            continue
        flops += sum(f for f, _ in _calls(run, kind))
        seconds += m[1]
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
