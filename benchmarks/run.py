"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit) and
writes JSON artifacts to experiments/bench/.

Two modes:

  figure suites     PYTHONPATH=src python benchmarks/run.py [filter]
  declarative jobs  PYTHONPATH=src python benchmarks/run.py \
                        --config configs/jobs/quickstart.json \
                        [--executor concurrent] [--workers 4] [--db out.jsonl]
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

# allow `python benchmarks/run.py` (script dir is on sys.path, repo root not)
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def run_config(args) -> None:
    from repro.core import (BenchmarkSession, ConcurrentFollowerExecutor,
                            InlineExecutor, PerfDB, PlanSpec)
    from repro.core.analysis import leaderboard, recommend

    executor = (ConcurrentFollowerExecutor() if args.executor == "concurrent"
                else InlineExecutor())
    session = BenchmarkSession(
        n_workers=args.workers,
        db=PerfDB(args.db) if args.db else None,
        executor=executor)
    handles = session.submit_file(args.config)
    print(f"# {len(handles)} jobs from {args.config} "
          f"({executor.name} executor, {args.workers} followers)")
    t0 = time.time()
    results = session.run()
    print(f"# executed {len(results)} jobs in {time.time()-t0:.1f}s")
    print(leaderboard(session.db, sort_by="throughput_rps", limit=20))
    slos = sorted({r.spec.slo_latency_s for r in results
                   if getattr(r.spec, "slo_latency_s", None) is not None
                   and not isinstance(r.spec, PlanSpec)})
    for slo in slos:
        print(f"\n# top configs under p99 <= {slo*1e3:.0f} ms:")
        for rec in recommend(session.db, slo_latency_s=slo):
            print(f"#   {rec['job_id']:24s} policy={rec['policy']:5s} "
                  f"chips={rec['chips']}")
    if args.db:
        print(f"# PerfDB records appended to {args.db}")


def run_suites(only) -> None:
    from benchmarks import (bench_cluster, bench_cost,
                            bench_dynamic_batching, bench_kernels,
                            bench_latency_throughput, bench_pipeline,
                            bench_roofline, bench_scheduler,
                            bench_sensitivity, bench_tail_latency)
    suites = [
        ("fig7_latency_throughput", bench_latency_throughput.run),
        ("fig8_cost", bench_cost.run),
        ("fig9_sensitivity", bench_sensitivity.run),
        ("fig10_roofline", bench_roofline.run),
        ("fig11_tail_latency", bench_tail_latency.run),
        ("fig12_dynamic_batching", bench_dynamic_batching.run),
        ("fig14_pipeline", bench_pipeline.run),
        ("fig15_scheduler", bench_scheduler.run),
        ("cluster_scale", bench_cluster.run),
        ("kernels_micro", bench_kernels.run),
    ]
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and only not in name:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        fn()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("filter", nargs="?", default=None,
                        help="substring filter for figure suites")
    parser.add_argument("--config", default=None,
                        help="JSON/TOML job or sweep config to execute")
    parser.add_argument("--executor", choices=("inline", "concurrent"),
                        default="concurrent")
    parser.add_argument("--workers", type=int, default=4)
    parser.add_argument("--db", default=None,
                        help="PerfDB JSONL path to append records to")
    args = parser.parse_args()
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    if args.config:
        run_config(args)
    else:
        run_suites(args.filter)


if __name__ == "__main__":
    main()
