"""One cell's server and window, shared by the benchmark and its tools."""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import jax

from bench import adapter, generator, weights

DRAIN_S = 60.0      # an open loop's requests are answered by then or fail


@dataclasses.dataclass
class Served:
    records: List
    batches: List
    compiles: int       # backend compilations inside the window
    attempted: int      # requests due in the window (open) or sent (backlog)

    @property
    def unanswered(self) -> int:
        return (sum(r.tokens is None for r in self.records)
                + self.attempted - len(self.records))


def backlog(cell) -> bool:
    return cell.mix["arrivals"] == generator.BACKLOG


def build(cell, seed: int) -> adapter.Server:
    """Weights from the seed on the device, and every shape the cell's
    traffic uses compiled (or loaded from the cache) and run once.  A
    backlog keeps the largest batch filled, so only that size is used."""
    server = adapter.Server(adapter.model_config(cell.config),
                            new_params(cell, seed), cell.pad, cell.max_len)
    preferred = cell.params["batcher"]["preferred"]
    server.warm([max(preferred)] if backlog(cell) else sorted(preferred))
    return server


def new_params(cell, seed: int):
    return adapter.params_fn(cell.config, cell.dims)(weights.seed_words(seed))


def serve(server: adapter.Server, cell, seed: int, seconds: float,
          rate: Optional[float] = None, trace_dir: Optional[str] = None,
          order: int = generator.SCHEDULE_SEED) -> Served:
    """The cell's traffic from the seed, served for ``seconds``; with
    ``trace_dir``, the stretch ``trace_span_s`` before the close is traced.
    ``rate`` and ``order`` replace the cell's rate and schedule (sweeps)."""
    prm, mix, vocab = cell.params, cell.mix, cell.dims["vocab"]
    span = prm["trace_span_s"]
    loop = adapter.Loop(server, prm["batcher"],
                        adapter.Tracer(trace_dir, seconds - span, seconds))
    outstanding = mix.get("outstanding", 0) * max(prm["batcher"]["preferred"])
    if backlog(cell):
        requests = generator.stream(mix, seed, vocab)
    else:
        requests = generator.open_loop(mix, seed, vocab,
                                       rate or prm["rate"], seconds, order)
    try:
        with adapter.CompileCounter() as compiles:
            if backlog(cell):
                loop.backlog(requests, outstanding, seconds)
            else:
                loop.open_loop(requests, seconds + DRAIN_S)
    finally:
        loop.tracer.close()
    attempted = len(loop.records) if backlog(cell) else len(requests)
    return Served(loop.records, loop.batches, compiles.count, attempted)


def window(cell, seed: int, seconds: float, trace: bool, t_start: float,
           keep: Optional[str] = None):
    """Set-up and the window, timed from ``t_start``, the process's start.
    Returns (setup_s, served, peak bytes, trace summary) and frees the
    program's state on return.  With ``trace``, the stretch the cell
    names is profiled and reduced after the peak is read
    (``scopes.summarize`` over the base scopes and the family's); with
    ``keep``, the serialized trace is also written to
    ``<keep>/trace.xplane.pb``."""
    from bench import scopes, trace_reduce
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        server = build(cell, seed)
        setup_s = time.perf_counter() - t_start
        served = serve(server, cell, seed, seconds, trace_dir=trace_dir)
        peak = memory_peak()
        summary = None
        if trace:
            t0 = time.perf_counter()
            raw = trace_reduce.load(trace_dir)
            if keep:
                os.makedirs(keep, exist_ok=True)
                with open(os.path.join(keep, "trace.xplane.pb"), "wb") as f:
                    f.write(raw)
            summary = scopes.summarize(raw, scopes.BASE + cell.family.SCOPES)
            print(f"trace: {len(raw)} bytes reduced in "
                  f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return setup_s, served, peak, summary


def memory_peak() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
