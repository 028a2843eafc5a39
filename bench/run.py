"""Runs one benchmark cell on the chip and prints its result line.

  python3 bench/run.py --workload granite-3-2b.chat --seed 7 --seconds 51 --trace 0

One process holds the chip.  Set-up makes the configuration's weights on
the device from the seed, builds the program's jitted steps and warms
every shape the cell's traffic uses (compile cache at the fixed path the
program's ``repro.runtime.enable_compile_cache`` gives, inside the
checkout).  The window then serves the cell's traffic for ``--seconds``
through ``bench.adapter``.  Afterwards the device's peak memory is read,
the program's state is freed, and a sample of the served tokens is checked
against the plain reference (``bench.check``).  With ``--trace 1`` a
stretch at the window's end is profiled, reduced to device time by step,
op and the program's scopes (``bench.scopes``), and the cell's per-layer
metrics are printed; otherwise its end-to-end metrics.  The last line of standard
output is one JSON object; the numbers compared for ``correct`` are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits with 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: Dict,
             device: Dict) -> Dict:
    """One run of a cell: set-up, window, check; the result line's object."""
    from bench import cellrun, check, spec
    from bench.measures import Run
    setup_s, served, peak, summary = cellrun.window(cell, seed, seconds, trace,
                                                    T_START)
    chk = cell.params["check"]
    picked = check.sample(served.records, seed, chk["served_tokens"])
    gap = (check.served_gaps(cell, seed, picked)["served"]
           if picked else float("inf"))
    print(f"checked {sum(r.req.out_len for r in picked)} served tokens of "
          f"{len(picked)} requests", file=sys.stderr)
    checks = check.checks(gap, served, chk["max_logit_gap"])
    run = Run(family=cell.family, dims=cell.dims, seconds=seconds,
              setup_s=setup_s, records=served.records,
              batches=served.batches, peaks=peaks, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=peak)
    out = {"correct": check.correct(checks),
           "attempted": served.attempted, "failed": served.unanswered,
           "metrics": metrics, "device": dev}
    if summary:
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < cell.entry["chips"]:
        print(f"{len(devices)} chips, the cell asks for {cell.entry['chips']}",
              file=sys.stderr)
        return 1
    peaks = spec.peaks(devices[0].device_kind)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                   device)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
