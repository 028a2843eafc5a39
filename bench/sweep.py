"""Finds an open-loop cell's knee: serves it at a ladder of rates.

  python3 bench/sweep.py --workload granite-3-2b.chat --seed 5 --seconds 30 --rates 2,3,4
  python3 bench/sweep.py --workload granite-3-2b.chat --seed 5 --seconds 51 --rates 3 --orders 1,2,3

One process and one set-up; each rate (and each schedule order, where
``--orders`` names several) is a window of its own with its own seed.  Per rate it prints one JSON line: requests due and answered by the
close, the answered rate, time-to-first-token quantiles, and the mean of
the first and of the second half of the window's requests.  A rate is
sustained where nearly every request due is answered by the close and the
second half waits no longer than the first: the queue does not grow.  The
knee is the highest sustained rate; a chat cell runs at 4/5 of it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.generator import SCHEDULE_SEED  # noqa: E402


def summary(served, cell, seconds: float, rate: float, order: int) -> dict:
    import numpy as np
    from bench import spec
    from bench.measures import Run
    recs = sorted(served.records, key=lambda r: r.req.due)
    ttft = np.array([r.first_token - r.req.due for r in recs])
    half = len(recs) // 2
    run = Run(family=cell.family, dims=cell.dims, seconds=seconds,
              setup_s=0.0, records=served.records, batches=served.batches,
              peaks={})
    return {"rate": rate, "order": order, "due": served.attempted,
            "answered_by_close": sum(r.done <= seconds for r in recs),
            "answered_rate": sum(r.done <= seconds for r in recs) / seconds,
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_mean_first_half_s": float(ttft[:half].mean()),
            "ttft_mean_second_half_s": float(ttft[half:].mean()),
            "mean_batch": float(np.mean([len(b.lengths)
                                         for b in served.batches])),
            "unanswered": served.unanswered, "compiles": served.compiles,
            **{m: spec.reader(m)(run) for m in
               ("ttft_p90_s", "tpot_p90_s", "queue_wait_p50_s")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", default=str(SCHEDULE_SEED))
    args = ap.parse_args(argv)
    from bench import cellrun, spec
    from repro.runtime import enable_compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load(args.workload)
    server = cellrun.build(cell, args.seed)
    windows = [(float(r), int(o)) for r in args.rates.split(",")
               for o in args.orders.split(",")]
    for i, (rate, order) in enumerate(windows):
        served = cellrun.serve(server, cell, args.seed + i, args.seconds,
                               rate=rate, order=order)
        print(json.dumps(summary(served, cell, args.seconds, rate, order)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
