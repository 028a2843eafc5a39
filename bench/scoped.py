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
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def breakdown(summary) -> dict:
    """Per step: calls, module, busy and self seconds, scopes largest
    first, and the costliest unscoped ops; and the trace's costliest ops
    with their scope (``summary`` as ``scopes.summarize`` gives it)."""
    from bench import scopes
    steps, named = {}, {}
    for step, by_scope in summary["scopes"].items():
        ops = summary["scope_ops"][step]
        calls, module_s = summary["modules"].get(step, (0, 0.0))
        unscoped = sorted(((o, t) for o, (s, t) in ops.items()
                           if s == scopes.UNSCOPED), key=lambda x: -x[1])
        steps[step] = {
            "calls": calls, "module_s": module_s,
            "busy_s": summary["step_busy_s"][step],
            "self_s": sum(by_scope.values()),
            "scopes": sorted(by_scope.items(), key=lambda x: -x[1]),
            "unscoped_ops": unscoped[:10]}
        for op, (scope, _) in ops.items():
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

    from bench import cellrun, scopes
    from bench.measures import Run
    setup_s, served, _, summary = cellrun.window(
        cell, args.seed, args.seconds, True, T_START, keep=args.keep)
    if args.keep:
        with open(os.path.join(args.keep, "batches.json"), "w") as f:
            json.dump([dataclasses.asdict(b) for b in served.batches], f)
    run = Run(family=cell.family, dims=cell.dims, seconds=args.seconds,
              setup_s=setup_s, records=served.records,
              batches=served.batches, peaks=peaks, trace=summary)
    metrics = {m["name"]: spec.reader(m["name"])(run)
               for m in cell.end_to_end + cell.per_layer}
    metrics.update({k: f(run) for k, f in scopes.READERS.items()})
    print(json.dumps({
        "cell": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices),
                   "busy_s": summary["busy_s"],
                   "window_s": summary["window_s"]},
        "compiles_in_window": served.compiles,
        "metrics": metrics, **breakdown(summary)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
