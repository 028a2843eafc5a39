"""Smoke test of the served path on one TPU chip, in one process.

    python chip_smoke.py

Phases, each printing one line, in order:

  (a) device   JAX must find a TPU, and the Pallas kernels must compile
               (not run in interpret mode);
  (b) serve    ``run_server`` serves granite-3-2b at its published widths
               in bf16 behind the TrIS batcher under Poisson traffic:
               every request answered, nothing compiled in the window;
  (c) cache    prefill + cached decode against one full forward pass,
               on logits;
  (d) job      a generated-model job through ``BenchmarkSession``; its
               record names the device that measured it;
  (e) kernels  every Pallas kernel at real widths against its reference.

The last line of standard output is one JSON object naming the device.
Any failed check raises, and the script exits non-zero without it.
Weights and inputs are random, made from fixed seeds.  The persistent
compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.runtime import enable_compile_cache  # noqa: E402

# bf16 activations through 40 layers: the cached and the full pass round
# differently, and random weights amplify it.  At 40 layers (width 256)
# the CPU shows max|diff| = 0.11·max|logit| in bf16 and 3e-5 in float32;
# a decode position off by one gives 1.0–1.3.
CACHE_RTOL = 0.25


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def phase_device():
    import jax
    from repro.kernels import ops
    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform!r})")
    check(not ops.interpret_mode(), "Pallas kernels would run interpreted")
    print(f"(a) device: {d.platform} {d.device_kind} x{len(devices)}, "
          f"kernels compiled (interpret mode off)", flush=True)
    return d, len(devices)


def phase_serve():
    from repro.configs import get_config
    from repro.launch.serve import run_server
    from repro.serving.batching import make_policy
    from repro.serving.engine import serving_config
    from repro.serving.workload import WorkloadSpec, generate

    cfg = get_config("granite-3-2b")
    check((cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.head_dim, cfg.d_ff, cfg.vocab_size)
          == (40, 2048, 32, 8, 64, 8192, 49_155), "not granite's widths")
    check(serving_config(cfg).param_dtype == "bfloat16", "weights not bf16")
    wl = WorkloadSpec(rate=4.0, duration_s=5.0, prompt_tokens=512, seed=0)
    offered = len(generate(wl))
    out = run_server(cfg, make_policy("tris", preferred=(8, 4, 2, 1)), wl,
                     max_len=1024, decode_steps=64)
    check(out["requests"] == offered,
          f"answered {out['requests']} of {offered} requests")
    check(out["compiles_in_window"] == 0,
          f"{out['compiles_in_window']} compilations in the window")
    check(out["p50_s"] > 0 and out["mean_infer_s"] > 0, "no latency")
    print(f"(b) serve: granite-3-2b bf16, {out['requests']}/{offered} "
          f"requests, compile_s={out['compile_s']} "
          f"compiles_in_window={out['compiles_in_window']} "
          f"p50_s={out['p50_s']} p99_s={out['p99_s']} "
          f"mean_batch={out['mean_batch']} "
          f"mean_infer_s={out['mean_infer_s']} "
          f"throughput_rps={out['throughput_rps']}", flush=True)


def phase_cache():
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import cached_and_full_logits, serving_config

    cfg = serving_config(get_config("granite-3-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 520), 0,
                                cfg.vocab_size)
    cached, full = cached_and_full_logits(model, params, tokens,
                                          prompt_len=512, max_len=1024)
    cached, full = np.asarray(cached), np.asarray(full)
    check(cached.shape == full.shape == (2, 9, cfg.vocab_size),
          f"logits shapes {cached.shape} {full.shape}")
    check(bool(np.isfinite(cached).all() and np.isfinite(full).all()),
          "non-finite logits")
    diff = float(np.abs(cached - full).max())
    scale = float(np.abs(full).max())
    check(diff <= CACHE_RTOL * scale,
          f"cached decode is {diff} off the full pass (max logit {scale})")
    print(f"(c) cache: prefill 512 + 8 decode steps vs forward over 520 "
          f"tokens, 2 prompts: max|diff|={diff} max|logit|={scale} "
          f"ratio={diff / scale} (tolerance {CACHE_RTOL})", flush=True)


def phase_job():
    import math
    from repro.core import (BenchmarkJobSpec, BenchmarkSession,
                            InlineExecutor, ModelRef)

    session = BenchmarkSession(n_workers=1, executor=InlineExecutor())
    handle = session.submit(BenchmarkJobSpec(
        job_id="chip-smoke-generated",
        model=ModelRef(kind="generated", family="transformer", layers=4,
                       width=256, batch_hint=8)))
    session.run()
    m = handle.result().metrics
    check(m["mode"] == "measured-tpu", f"job labelled {m['mode']!r}")
    check(math.isfinite(m["latency_s"]) and m["latency_s"] > 0,
          f"latency {m['latency_s']}")
    print(f"(d) job: generated transformer-L4-W256 batch 8, "
          f"mode={m['mode']} device_kind={m['device_kind']} "
          f"latency_s={m['latency_s']} "
          f"attained_flops={m['attained_flops']}", flush=True)


def phase_kernels():
    import jax
    import numpy as np
    from repro.kernels.cases import CASES

    errs = []
    for name, case in sorted(CASES.items()):
        args = case.make(jax.random.key(0))
        got = jax.jit(lambda *a: case.kernel(*a, interpret=False))(*args)
        with jax.default_matmul_precision("float32"):
            want = jax.jit(case.ref)(*args)
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g = np.asarray(g, np.float64)
            w = np.asarray(w, np.float64)
            check(bool(np.isfinite(g).all()), f"{name}: non-finite output")
            check(bool((np.abs(g - w) <= case.atol + case.rtol * np.abs(w))
                       .all()), f"{name}: off its reference")
            worst = max(worst, float(np.abs(g - w).max()))
        errs.append(f"{name}={worst}")
    print(f"(e) kernels vs reference, max|err|: {' '.join(errs)}",
          flush=True)


def main() -> None:
    enable_compile_cache()
    t0 = time.perf_counter()
    device, count = phase_device()
    phase_serve()
    phase_cache()
    phase_job()
    phase_kernels()
    print(f"# all phases passed in {time.perf_counter() - t0} s",
          file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
