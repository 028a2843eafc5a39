"""Serves one cell with the trace on and prints where each step's device
time goes, by the program's ``named_scope`` names (``bench.scopes``).

  python3 bench/scoped.py --workload granite-3-2b.chat --seed 7 --seconds 51 [--keep DIR]

Set-up and window as ``bench/run.py --trace 1`` makes them, with no check
against the reference.  The last line of standard output is one JSON
object: the cell's end-to-end and per-layer metrics and the scope metrics
of ``bench.scopes.READERS`` read from the traced run; for each served step
its calls, module and busy seconds, the sum of its ops' self seconds, and
its scopes largest first; the ten costliest unscoped ops of each step; and
the trace's costliest ops named ``<step>/<scope>|<op>``.  With ``--keep``
the trace (``trace.xplane.pb``) and the traced batches (``batches.json``)
are copied into DIR.  Without a TPU it exits with 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

def _keep(raw: bytes, served, keep: str) -> None:
    os.makedirs(keep, exist_ok=True)
    with open(os.path.join(keep, "trace.xplane.pb"), "wb") as f:
        f.write(raw)
    with open(os.path.join(keep, "batches.json"), "w") as f:
        json.dump([dataclasses.asdict(b) for b in served.batches], f)


def breakdown(summary, red) -> dict:
    """Per step: calls, module, busy and self seconds, scopes largest
    first, and the costliest unscoped ops; and the trace's costliest ops
    with their scope."""
    from bench import scopes
    steps, named = {}, {}
    for step, by_scope in red["scopes"].items():
        calls, module_s = summary["modules"].get(step, (0, 0.0))
        unscoped = sorted(((o, t) for o, (s, t) in red["ops"][step].items()
                           if s == scopes.UNSCOPED), key=lambda x: -x[1])
        steps[step] = {
            "calls": calls, "module_s": module_s,
            "busy_s": red["busy_s"][step],
            "self_s": sum(by_scope.values()),
            "scopes": sorted(by_scope.items(), key=lambda x: -x[1]),
            "unscoped_ops": unscoped[:10]}
        for op, (scope, _) in red["ops"][step].items():
            named.setdefault(op, f"{step.rsplit('_', 1)[1]}/{scope}")
    ops = [[f"{named.get(op, scopes.UNSCOPED)}|{op}", t]
           for op, t in summary["device_ops"]]
    return {"steps": steps, "device_ops": ops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the trace and batches here")
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        return 1
    peaks = spec.peaks(devices[0].device_kind)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from jax.profiler import ProfileData
    from bench import cellrun, scopes, trace_reduce
    from bench.measures import Run
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        server = cellrun.build(cell, args.seed)
        setup_s = time.perf_counter() - T_START
        served = cellrun.serve(server, cell, args.seed, args.seconds,
                               trace_dir=trace_dir)
        raw = scopes.load(trace_dir)
        if args.keep:
            _keep(raw, served, args.keep)
        summary = trace_reduce.reduce(ProfileData.from_serialized_xspace(raw))
        red = scopes.reduce(raw)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    summary["scopes"] = red["scopes"]
    run = Run(dims=cell.dims, seconds=args.seconds, setup_s=setup_s,
              records=served.records, batches=served.batches, peaks=peaks,
              trace=summary)
    metrics = {m["name"]: spec.reader(m["name"])(run)
               for m in cell.end_to_end + cell.per_layer}
    metrics.update({k: f(run) for k, f in scopes.READERS.items()})
    print(json.dumps({
        "cell": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices),
                   "busy_s": summary["busy_s"],
                   "window_s": summary["window_s"]},
        "compiles_in_window": served.compiles,
        "metrics": metrics, **breakdown(summary, red)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
