"""Readings that set a cell's ``max_logit_gap`` limit, and its control.

  python3 bench/calibrate.py --workload granite-3-2b.chat --seconds 12 --seeds 1,2,3

One process and one set-up.  For each seed: weights made anew from it, a
short window at the cell's own load, the same sample a run checks, and two
readings at the same prompts and served tokens: the widest gap of the
served tokens below the float32 reference's best (the program's reading),
and the widest gap of the tokens an fp8 reference puts first (the
control's reading), each also judged as a run judges it against the
cell's limit (``correct`` and ``control_correct``, which has to come out
false).  The limit lies between the largest program reading and the
smallest control reading.  The first seed also checks that the
reference makes the program's weights again bit for bit.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def same_weights(server, cell, seed: int) -> bool:
    """The reference's layer 1 and global leaves, laid out as the program's
    tree by the family, against the program's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import check, weights as W
    ref = check.reference(cell.config["family"])
    key, dtype = tuple(sorted(cell.dims.items())), cell.config["dtype"]
    words = W.seed_words(seed)
    want = cell.family.program_params(
        {"layers": ref._layer_weights(words, np.int32(1), key, dtype),
         **ref._global_weights(words, key, dtype)})
    p = server.params
    got = dict(p, layers=jax.tree.map(lambda a: a[1], p["layers"]))
    return jax.tree.structure(want) == jax.tree.structure(got) and all(
        bool(jnp.array_equal(a, b.astype(jnp.float32)))
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import cellrun, check, spec
    from repro.runtime import enable_compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    server = cellrun.build(cell, seeds[0])
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            server.params = None
            server.params = cellrun.new_params(cell, seed)
        ok = same_weights(server, cell, seed) if i == 0 else None
        served = cellrun.serve(server, cell, seed, args.seconds)
        server.params = None            # the reference runs alone
        picked = check.sample(served.records, seed,
                              cell.params["check"]["served_tokens"])
        gaps = check.served_gaps(cell, seed, picked, control=True)
        limit = cell.params["check"]["max_logit_gap"]
        row = dict(seed=seed, requests=len(picked), **gaps,
                   unanswered=served.unanswered, compiles=served.compiles,
                   correct=check.correct(check.checks(gaps["served"], served,
                                                      limit)),
                   control_correct=check.correct(
                       check.checks(gaps["control"], served, limit)))
        if ok is not None:
            row["reference_weights_equal"] = ok
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"lower": max(r["served"] for r in rows),
                      "upper": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
