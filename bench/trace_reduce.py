"""From a profiler trace to device busy and idle time, per-module device
time, the costliest device operations, and idle gaps labelled by the
harness span that was open.

The traced window runs from the start of the first ``bench.*`` host span
to the end of the last.  Busy time is the union of the intervals of the
device's ``XLA Ops`` events inside that window, averaged over the devices
traced.  Module time is the sum of the ``XLA Modules`` events of each
jitted program (``jit_serve_decode(12)`` counts under ``jit_serve_decode``).
An idle gap is a stretch of the window in which no operation runs on the
device; it is named after the innermost ``bench.*`` span open at its middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
MODULES = "XLA Modules"
OPS = "XLA Ops"
TOP = 10
_SUFFIX = re.compile(r"\(\d+\)$")


def load(trace_dir: str) -> bytes:
    """The serialized trace the profiler wrote under ``trace_dir``: what
    ``ProfileData.from_serialized_xspace`` reads, and the op metadata that
    ``bench.scopes`` reads beside it."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    with open(paths[0], "rb") as f:
        return f.read()


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(spans, starts, t: float) -> str:
    """The innermost harness span open at t: of those that cover t, the
    one that started last.  ``spans`` is sorted by start."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i][1] >= t:
            return spans[i][2]
    return "outside"


def reduce(pd: ProfileData) -> Optional[Dict]:
    """The trace's summary, or None where it holds no device or no span."""
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({ln.name: list(ln.events) for ln in plane.lines})
            continue
        for line in plane.lines:
            spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    if not spans or not devices:
        return None
    spans.sort()
    starts = [s for s, _, _ in spans]
    lo, hi = starts[0], max(e for _, e, _ in spans)
    busy_ns, modules, ops = 0.0, {}, collections.Counter()
    gaps = collections.Counter()
    for lines in devices:
        busy = _merge(_clip([(e.start_ns, e.end_ns)
                             for e in lines.get(OPS, [])], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_label(spans, starts, (s + e) / 2)] += (e - s) * 1e-9
        for e in lines.get(OPS, []):
            if lo <= e.start_ns <= hi:
                ops[e.name] += e.duration_ns * 1e-9
        for e in lines.get(MODULES, []):
            if lo <= e.start_ns <= hi:
                n, t = modules.get(_SUFFIX.sub("", e.name), (0, 0.0))
                modules[_SUFFIX.sub("", e.name)] = (n + 1,
                                                    t + e.duration_ns * 1e-9)
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "modules": {k: (n // n_dev, t / n_dev) for k, (n, t) in modules.items()},
        "device_ops": [[k, v / n_dev] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[k, v / n_dev] for k, v in gaps.most_common(TOP)],
    }


def module(summary: Optional[Dict], name: str) -> Optional[Tuple[int, float]]:
    """(calls, device seconds) of one jitted program in the trace."""
    if not summary:
        return None
    return summary["modules"].get(name)
