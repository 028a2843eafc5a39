"""Device time of the served steps by the model's own names.

The program names its parts with ``jax.named_scope``; XLA keeps the name
stack in each instruction's ``op_name`` metadata
(``jit(serve_decode)/while/body/closed_call/attn_core/dot_general``), and
the TPU profiler carries it as the ``tf_op`` stat (``<path>:<type>``) of
each ``XLA Ops`` event's metadata.  ``ProfileData`` does not show metadata
stats, so they are read from the serialized trace with a schema of the few
XSpace fields they need (``_space``).  An op's scope is the
innermost component of that path that is among the names it is given:
``BASE``, the scopes every served step carries, and the ``SCOPES`` of the
cell's family (``bench/families/<family>.py``); an op with none is
``unscoped``.  A fusion of instructions from several scopes carries its
root's metadata, so it counts under its root's scope.

An op's self time is its duration less the part that ops nested inside it
on the same line cover: a ``while`` gets only what its body's ops leave
uncovered.  So the self times of a step's ops add up to the union of their
intervals, the step's busy time, with nothing counted twice.  Only ops
inside an ``XLA Modules`` event of a served step that starts in the traced
window (the harness's ``bench.*`` spans, as ``trace_reduce`` has it) count.
"""
from __future__ import annotations

import bisect
import collections
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from jax.profiler import ProfileData

from bench import costs, trace_reduce
from bench.measures import DECODE, PREFILL, decode_contexts, step_time
from bench.trace_reduce import (DEVICE_PREFIX, MODULES, OPS, SPAN_PREFIX,
                                _SUFFIX, _merge)

# named outside the layers: the embedding, the output head, sampling
BASE = ("embed", "logits", "sample")
UNSCOPED = "unscoped"
STEPS = (PREFILL, DECODE)
PATH_STAT = "tf_op"


def scope_of(path: str, names: Sequence[str]) -> str:
    """The innermost component of an op-name path that is in ``names``."""
    for part in reversed(path.split("/")):
        if part in names:
            return part
    return UNSCOPED


def self_times(ops: Sequence[Tuple[float, float]]) -> List[float]:
    """Each interval's length less what the intervals nested in it cover.

    ``ops`` are (start, end) on one line, where two either nest or do not
    meet; the result is in the same order."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out = [e - s for s, e in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(e, ops[stack[-1]][1]) - s
        stack.append(i)
    return out


def _window(pd) -> Optional[Tuple[float, float]]:
    """From the first harness span's start to the last one's end."""
    spans = [(e.start_ns, e.end_ns) for plane in pd.planes
             if not plane.name.startswith(DEVICE_PREFIX)
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIX)]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


# The XSpace messages and fields (``tsl/profiler/protobuf/xplane.proto``)
# that carry event metadata: (message, [(field, number, type)]), where a
# message type is repeated unless it is a map entry's value.
_XSPACE = (
    ("Stat", [("metadata_id", 1, "int64"), ("str_value", 5, "bytes")]),
    ("EventMetadata", [("name", 2, "bytes"), ("stats", 5, "Stat")]),
    ("EventMetadataEntry", [("value", 2, "EventMetadata")]),
    ("StatMetadata", [("name", 2, "bytes")]),
    ("StatMetadataEntry", [("key", 1, "int64"), ("value", 2, "StatMetadata")]),
    ("Plane", [("name", 2, "bytes"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")]),
    ("Space", [("planes", 1, "Plane")]),
)


@functools.lru_cache(maxsize=None)
def _space():
    """A message class that parses an XSpace into the fields of ``_XSPACE``
    and skips every other."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                               package="bench_xspace")
    for name, fields in _XSPACE:
        msg = proto.message_type.add(name=name)
        for fname, number, kind in fields:
            field = msg.field.add(name=fname, number=number,
                                  label=F.LABEL_OPTIONAL)
            if kind in ("int64", "bytes"):
                field.type = getattr(F, "TYPE_" + kind.upper())
                continue
            field.type, field.type_name = F.TYPE_MESSAGE, f".bench_xspace.{kind}"
            if fname != "value":
                field.label = F.LABEL_REPEATED
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space"))


def metadata_paths(serialized: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op-name path}} from the ``tf_op`` stat
    of each event's metadata, its ``:<type>`` suffix dropped."""
    out = {}
    for plane in _space().FromString(serialized).planes:
        name = plane.name.decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        want = {e.key for e in plane.stat_metadata
                if e.value.name.decode() == PATH_STAT}
        out[name] = {
            entry.value.name.decode(errors="replace"):
                st.str_value.decode(errors="replace").rsplit(":", 1)[0]
            for entry in plane.event_metadata for st in entry.value.stats
            if st.metadata_id in want}
    return out


def summarize(serialized: bytes, names: Sequence[str]) -> Optional[Dict]:
    """``trace_reduce.reduce``'s summary of the trace, with each served
    step's self seconds by scope and by op, and its busy seconds, averaged
    over the devices traced: ``"scopes": {step: {scope: s}}``,
    ``"scope_ops": {step: {op: [scope, s]}}``, ``"step_busy_s": {step: s}``.
    None where the trace holds no device or no harness span."""
    pd = ProfileData.from_serialized_xspace(serialized)
    summary = trace_reduce.reduce(pd)
    red = _reduce(pd, serialized, frozenset(names))
    if summary is not None and red is not None:
        summary.update(red)
    return summary


def _reduce(pd, serialized: bytes, names) -> Optional[Dict]:
    window = _window(pd)
    devices = [plane for plane in pd.planes
               if plane.name.startswith(DEVICE_PREFIX)]
    if window is None or not devices:
        return None
    lo, hi = window
    scopes = collections.defaultdict(collections.Counter)
    ops = collections.defaultdict(dict)
    busy = collections.Counter()
    paths = metadata_paths(serialized)
    for plane in devices:
        named = paths.get(plane.name, {})
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, _SUFFIX.sub("", e.name))
                      for e in lines.get(MODULES, [])
                      if lo <= e.start_ns <= hi
                      and _SUFFIX.sub("", e.name) in STEPS)
        starts = [s for s, _, _ in mods]
        mine = collections.defaultdict(list)
        for e in lines.get(OPS, []):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < mods[i][1]:
                mine[mods[i][2]].append(e)
        for step, events in mine.items():
            spans = [(e.start_ns, e.end_ns) for e in events]
            busy[step] += sum(e - s for s, e in _merge(spans)) * 1e-9
            for e, t in zip(events, self_times(spans)):
                scope = scope_of(named.get(e.name, ""), names)
                scopes[step][scope] += t * 1e-9
                _, seen = ops[step].get(e.name, (scope, 0.0))
                ops[step][e.name] = [scope, seen + t * 1e-9]
    n = len(devices)
    return {"scopes": {k: {s: t / n for s, t in v.items()}
                       for k, v in scopes.items()},
            "scope_ops": {k: {o: [s, t / n] for o, (s, t) in v.items()}
                          for k, v in ops.items()},
            "step_busy_s": {k: t / n for k, t in busy.items()}}


# ---------------------------------------------------------------- readers
def scope_s(run, step: str, scope: str) -> Optional[float]:
    """Self seconds of one scope over the traced calls of a step; None
    where the trace has no scopes or its calls disagree with the
    harness's (``measures.step_time``)."""
    t = run.trace
    if not t or "scopes" not in t or step_time(run, step) is None:
        return None
    return t["scopes"].get(step, {}).get(scope, 0.0)


def ms_per_call(run, step: str, scope: str) -> Optional[float]:
    t = scope_s(run, step, scope)
    return None if t is None else 1e3 * t / step_time(run, step)[0]


def decode_attn_roofline(run) -> Optional[float]:
    """Least time of the traced decode calls' attention (live pairs at
    peak, or live entries at peak bandwidth, whichever is longer), as a
    share (%) of their ``attn_core`` time."""
    t = scope_s(run, DECODE, "attn_core")
    if not t:
        return None
    counts = run.family.decode_attn_counts
    bound = sum(costs.bound_s(*counts(run.dims, c), run.peaks)
                for c in decode_contexts(run))
    return 100.0 * bound / t


def prefill_attn_roofline(run) -> Optional[float]:
    """Causal-pair operations of the traced prefills' live prompt tokens at
    peak, as a share (%) of their ``attn_core`` time."""
    t = scope_s(run, PREFILL, "attn_core")
    if not t:
        return None
    flops = sum(run.family.prefill_attn_flops(run.dims, b.lengths)
                for b in run.batches if b.traced)
    return 100.0 * flops / run.peaks["bf16_flops"] / t


READERS = {
    "decode_attn_ms": lambda run: ms_per_call(run, DECODE, "attn_core"),
    "decode_kv_ms": lambda run: ms_per_call(run, DECODE, "kv_write"),
    "decode_unscoped_ms": lambda run: ms_per_call(run, DECODE, UNSCOPED),
    "decode_attn_roofline": decode_attn_roofline,
    "prefill_attn_ms": lambda run: ms_per_call(run, PREFILL, "attn_core"),
    "prefill_attn_roofline": prefill_attn_roofline,
    "prefill_logits_ms": lambda run: ms_per_call(run, PREFILL, "logits"),
}
