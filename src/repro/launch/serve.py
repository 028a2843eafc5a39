"""End-to-end serving driver: REAL execution of a catalog model behind the
dynamic batcher, driven by a generated workload trace.

Requests arrive per the workload spec; the batcher groups them; the engine
runs actual jitted prefill + decode steps on the first device, with bf16
(serving) weights at the config's published widths, and wall-clock times
are recorded per stage.  ``--reduced`` swaps in the tiny same-family
config for runs on the CPU.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --policy tris --rate 4 --duration 5
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import build_model, reduced
from repro.runtime import device_info, enable_compile_cache
from repro.serving.batching import QueuedRequest, make_policy
from repro.serving.engine import (make_decode_fn, make_prefill_fn,
                                  serving_config)
from repro.serving.workload import WorkloadSpec, generate

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def run_server(cfg, policy, workload: WorkloadSpec, *,
               max_len: int = 192, decode_steps: int = 8) -> Dict:
    model = build_model(serving_config(cfg))
    # built under jit so only the serving-dtype weights land on the device
    params = jax.jit(model.init)(jax.random.key(0))
    prefill = jax.jit(make_prefill_fn(model, max_len=max_len))
    decode = jax.jit(make_decode_fn(model), donate_argnums=(1,))

    trace = generate(workload)
    # warmup compile for the batch sizes the policy can emit
    warm_sizes = sorted({1, getattr(policy, "max_batch", 1),
                         *getattr(policy, "preferred", (1,))})
    t_warm = time.perf_counter()
    for b in warm_sizes:
        toks = jnp.ones((b, workload.prompt_tokens), jnp.int32)
        lens = jnp.full((b,), workload.prompt_tokens, jnp.int32)
        cache, logits = prefill(params, toks, lens)
        cache, logits = decode(params, cache,
                               jnp.argmax(logits, -1).astype(jnp.int32))
        jax.block_until_ready(logits)
    compile_s = time.perf_counter() - t_warm

    compiles: List[str] = []
    def on_compile(event: str, duration: float, **kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    t_start = time.perf_counter()
    clock = lambda: time.perf_counter() - t_start
    queue: List[QueuedRequest] = []
    i, n = 0, len(trace)
    lat: List[float] = []
    batch_sizes: List[int] = []
    infer_times: List[float] = []
    while i < n or queue:
        now = clock()
        while i < n and trace[i].arrival_s <= now:
            queue.append(QueuedRequest(request=trace[i], enqueue_s=now))
            i += 1
        decision = policy.next_batch(queue, now, now)
        if decision is None:
            if i < n:
                time.sleep(max(trace[i].arrival_s - clock(), 0.0) + 1e-4)
            elif queue:
                time.sleep(1e-3)
            continue
        batch, _ = decision
        ids = {q.request.req_id for q in batch}
        queue = [q for q in queue if q.request.req_id not in ids]
        b = len(batch)
        toks = jnp.ones((b, workload.prompt_tokens), jnp.int32)
        lens = jnp.full((b,), workload.prompt_tokens, jnp.int32)
        t0 = time.perf_counter()
        cache, logits = prefill(params, toks, lens)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        for _ in range(decode_steps - 1):
            cache, logits = decode(params, cache, nxt)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        done = clock()
        infer_times.append(dt)
        batch_sizes.append(b)
        for q in batch:
            lat.append(done - q.request.arrival_s)
    jax.monitoring.unregister_event_duration_listener(on_compile)
    lat_arr = np.array(lat)
    return {
        "device": device_info(),
        "compile_s": compile_s,
        "compiles_in_window": len(compiles),
        "requests": len(lat),
        "throughput_rps": len(lat) / max(clock(), 1e-9),
        "p50_s": float(np.percentile(lat_arr, 50)) if len(lat) else 0.0,
        "p99_s": float(np.percentile(lat_arr, 99)) if len(lat) else 0.0,
        "mean_batch": float(np.mean(batch_sizes)) if batch_sizes else 0.0,
        "mean_infer_s": float(np.mean(infer_times)) if infer_times else 0.0,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (CPU runs)")
    ap.add_argument("--policy", default="tris",
                    choices=["none", "tfs", "tris"])
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--prompt-tokens", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    cfg = reduced(cfg) if args.reduced else cfg
    policy = make_policy(args.policy, **(
        dict(max_batch=args.max_batch, timeout_s=0.01)
        if args.policy == "tfs" else
        dict(preferred=(args.max_batch, 4, 2, 1)) if args.policy == "tris"
        else {}))
    wl = WorkloadSpec(rate=args.rate, duration_s=args.duration,
                      prompt_tokens=args.prompt_tokens, seed=0)
    out = run_server(cfg, policy, wl, decode_steps=args.decode_steps)
    print(f"arch={cfg.name} policy={args.policy} rate={args.rate}")
    for k, v in out.items():
        print(f"  {k:16s} {v:.4f}" if isinstance(v, float) else f"  {k:16s} {v}")


if __name__ == "__main__":
    main()
