"""Pallas-kernel calibration backend (measure → model, hardware edition).

The prefill/decode microbenches clock whole generated models; this
backend clocks the repo's own Pallas kernels (``repro.kernels``) over a
(batch × seq × dtype) grid and feeds the results into the same
PerfDB → fit → profile pipeline, so the planner's latency model is
anchored to the hardware-shaped code the serving engine actually runs.

Per grid point one ``kind="calibration"`` record is emitted carrying
the kernel name, dtype and ``backend`` provenance: ``"pallas-kernel"``
where the kernels compile for a chip, ``"pallas-interpret"`` where they
run in interpret mode on the CPU (see :func:`backend_label`).
Timing target:

  * **CPU (this container)** — the pure-jnp references are wall-clocked
    (they are the numerics the interpret-mode kernels validate against;
    interpret-mode Pallas itself runs a Python grid loop whose overhead
    would swamp any scaling signal).  Each (kernel, dtype) is still
    executed once through the real ``repro.kernels.ops`` entry point at
    the smallest grid shape and checked ``allclose`` against its
    reference, so every record is backed by a verified kernel.
  * **TPU** — the compiled Mosaic kernels are clocked directly
    (``target="kernel"`` is forced automatically off-CPU).

Per-kernel coefficients are fit with the existing least-squares designs
(:func:`repro.calibrate.fit.fit_phase`): sequence kernels (flash
attention, wkv6, rglru, int8 matmul) use the prefill design
``t = c0 + c1·(b·s) + c2·(b·s²)``; decode attention uses the decode
design ``t = c0 + α·b + β·(b·T)``.  The fits land in
``CalibrationProfile.kernels`` and the derived serving
:class:`~repro.serving.latency_model.SpeedMode` parameter dicts in
``CalibrationProfile.speed_modes``, which the capacity planner's
``speed_modes`` grid axis resolves before the built-in presets.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from repro.calibrate.fit import fit_phase
from repro.calibrate.profile import CalibrationProfile

KERNEL_BACKENDS = ("pallas-kernel", "pallas-interpret")

#: allclose tolerance per dtype for the kernel-vs-reference check
#: (matches tests/test_kernels.py)
VERIFY_TOL = {"float32": 2e-5, "bfloat16": 2e-2, "int8": 2e-5}

DEFAULT_BATCHES = (1, 2, 4)
DEFAULT_SEQS = (64, 128, 256)
DEFAULT_DTYPES = ("float32", "bfloat16")


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One benchable kernel: how to build inputs and which fit design
    its latencies follow.

    Attributes:
        name: registry key ("flash_attention", ...).
        phase: fit design — "prefill" (cost grows with b·s and b·s²)
            or "decode" (cost grows with b and b·context).
        dtypes: dtypes this kernel sweeps (int8 matmul is int8-only).
        make: ``make(batch, seq, dtype, seed)`` → (args, static_kwargs)
            for both the kernel and its reference.
        kernel_fn: the jitted ``repro.kernels.ops`` entry point.
        ref_fn: the pure-jnp reference it must match.
    """
    name: str
    phase: str
    dtypes: Sequence[str]
    make: Callable[[int, int, str, int], tuple]
    kernel_fn: Callable
    ref_fn: Callable


def _rand(key, shape, dtype, scale=1.0):
    import jax
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _make_flash(batch: int, seq: int, dtype: str, seed: int):
    import jax
    heads, kv_heads, d = 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (batch, heads, seq, d), dtype)
    k = _rand(ks[1], (batch, kv_heads, seq, d), dtype)
    v = _rand(ks[2], (batch, kv_heads, seq, d), dtype)
    block = min(128, seq)
    return (q, k, v), {"causal": True, "block_q": block, "block_k": block}


def _make_decode(batch: int, context: int, dtype: str, seed: int):
    import jax
    import jax.numpy as jnp
    heads, kv_heads, d = 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (batch, heads, d), dtype)
    k = _rand(ks[1], (batch, kv_heads, context, d), dtype)
    v = _rand(ks[2], (batch, kv_heads, context, d), dtype)
    lengths = jnp.full((batch,), context, dtype=jnp.int32)
    return (q, k, v, lengths), {"block_k": min(512, context)}


def _make_wkv6(batch: int, seq: int, dtype: str, seed: int):
    import jax
    heads, n = 2, 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = _rand(ks[0], (batch, seq, heads, n), dtype)
    k = _rand(ks[1], (batch, seq, heads, n), dtype)
    v = _rand(ks[2], (batch, seq, heads, n), dtype)
    logw = -jax.nn.softplus(_rand(ks[3], (batch, seq, heads, n),
                                  "float32")).astype(dtype)
    u = _rand(ks[4], (heads, n), dtype)
    s0 = _rand(ks[5], (batch, heads, n, n), "float32")
    return (r, k, v, logw, u, s0), {"chunk": min(32, seq)}


def _make_rglru(batch: int, seq: int, dtype: str, seed: int):
    import jax
    width = 256
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = (0.2 + 0.7 * jax.random.uniform(ks[0], (batch, seq, width))
         ).astype(dtype)
    b = _rand(ks[1], (batch, seq, width), dtype)
    s0 = _rand(ks[2], (batch, width), "float32")
    return (a, b, s0), {"chunk": min(128, seq), "block_r": width}


def _make_int8_matmul(batch: int, seq: int, dtype: str, seed: int):
    import jax
    from repro.kernels import ref
    d_in, d_out = 512, 512
    m = batch * seq
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (m, d_in), dtype="float32")
    w = jax.random.normal(ks[1], (d_in, d_out), dtype="float32")
    x_q, sx = ref.quantize_rowwise(x)
    w_q_t, sw = ref.quantize_rowwise(w.T)
    return (x_q, w_q_t.T, sx, sw), {"bm": min(64, m),
                                    "bn": 128, "bk": 512}


def _registry() -> Dict[str, KernelCase]:
    from repro.kernels import ops, ref
    return {
        "flash_attention": KernelCase(
            "flash_attention", "prefill", DEFAULT_DTYPES, _make_flash,
            ops.flash_attention,
            lambda q, k, v, **kw: ref.mha_reference(
                q, k, v, causal=kw.get("causal", True),
                window=kw.get("window", 0),
                softcap=kw.get("softcap", 0.0))),
        "decode_attention": KernelCase(
            "decode_attention", "decode", DEFAULT_DTYPES, _make_decode,
            ops.decode_attention,
            lambda q, k, v, lengths, **kw: ref.decode_attention_reference(
                q, k, v, lengths)),
        "wkv6": KernelCase(
            "wkv6", "prefill", DEFAULT_DTYPES, _make_wkv6,
            ops.wkv6,
            lambda r, k, v, logw, u, s0, **kw: ref.wkv6_reference(
                r, k, v, logw, u, s0)),
        "rglru_scan": KernelCase(
            "rglru_scan", "prefill", DEFAULT_DTYPES, _make_rglru,
            ops.rglru_scan,
            lambda a, b, s0, **kw: ref.rglru_reference(a, b, s0)),
        "int8_matmul": KernelCase(
            "int8_matmul", "prefill", ("int8",), _make_int8_matmul,
            ops.int8_matmul,
            lambda x_q, w_q, sx, sw, **kw: ref.int8_matmul_reference(
                x_q, w_q, sx, sw)),
    }


@functools.lru_cache(maxsize=1)
def kernel_registry() -> Dict[str, KernelCase]:
    """Name → :class:`KernelCase` for every benchable Pallas kernel."""
    return _registry()


def _first_leaf(out):
    return out[0] if isinstance(out, tuple) else out


def _verify(case: KernelCase, dtype: str, batch: int, seq: int,
            seed: int) -> float:
    """Run the real ops entry point vs the reference at one shape;
    return the max abs error (raises if outside tolerance)."""
    import jax
    import numpy as _np
    args, kwargs = case.make(batch, seq, dtype, seed)
    got = _first_leaf(jax.block_until_ready(case.kernel_fn(*args, **kwargs)))
    want = _first_leaf(jax.block_until_ready(case.ref_fn(*args, **kwargs)))
    want64 = _np.asarray(want, dtype=_np.float64)
    err = float(_np.max(_np.abs(_np.asarray(got, dtype=_np.float64)
                                - want64)))
    # scale by output magnitude: kernels that accumulate over a long
    # contraction (int8 matmul) have proportionally larger abs error
    tol = VERIFY_TOL.get(dtype, 2e-2) \
        * max(1.0, float(_np.max(_np.abs(want64))))
    if err > tol:
        raise AssertionError(
            f"kernel {case.name!r} ({dtype}) disagrees with its reference "
            f"at batch={batch} seq={seq}: max_err={err:.3e} > tol={tol:g}")
    return err


def backend_label() -> str:
    """``backend`` provenance of this process's kernel records."""
    from repro.kernels import ops
    return "pallas-interpret" if ops.interpret_mode() else "pallas-kernel"


def resolve_target(target: str = "auto") -> str:
    """Which implementation the sweep clocks: "kernel" | "reference"."""
    if target in ("kernel", "reference"):
        return target
    from repro.kernels import ops
    return "reference" if ops.interpret_mode() else "kernel"


def kernel_records(kernels: Optional[Sequence[str]] = None, *,
                   batches: Sequence[int] = DEFAULT_BATCHES,
                   seqs: Sequence[int] = DEFAULT_SEQS,
                   dtypes: Optional[Sequence[str]] = None,
                   repeats: int = 3, target: str = "auto",
                   verify: bool = True, seed: int = 0,
                   meta: Optional[Dict[str, Any]] = None
                   ) -> List[Dict[str, Any]]:
    """Wall-clock the kernel grid; one PerfDB record per point.

    Records look like the model-sweep calibration records (``phase``,
    ``batch``, ``tokens``, ``result.latency_s``) so the same fitter
    consumes them, plus ``kernel``, ``dtype`` and
    ``backend`` provenance (:func:`backend_label`).
    """
    import jax
    from repro.serving.latency_model import MeasuredLatency

    reg = kernel_registry()
    names = list(kernels) if kernels else sorted(reg)
    unknown = [n for n in names if n not in reg]
    if unknown:
        raise KeyError(f"unknown kernels {unknown} (known: {sorted(reg)})")
    mode = resolve_target(target)
    backend = backend_label()
    meta = dict(meta or {})
    records: List[Dict[str, Any]] = []
    for name in names:
        case = reg[name]
        sweep_dtypes = tuple(dtypes) if dtypes else tuple(case.dtypes)
        sweep_dtypes = tuple(d for d in sweep_dtypes if d in case.dtypes) \
            or tuple(case.dtypes)
        for dt in sweep_dtypes:
            max_err = _verify(case, dt, min(batches), min(seqs), seed) \
                if verify else None
            for b in batches:
                for s in seqs:
                    args, kwargs = case.make(b, s, dt, seed)
                    if mode == "kernel":
                        fn = functools.partial(case.kernel_fn, **kwargs)
                    else:
                        fn = jax.jit(functools.partial(case.ref_fn,
                                                       **kwargs))
                    clock = MeasuredLatency(fn, warmup=1,
                                            iters=max(repeats, 1),
                                            reducer="min")
                    lat = clock.measure(*args)
                    rec = dict(meta, kind="calibration", phase=case.phase,
                               batch=int(b), tokens=int(s),
                               kernel=name, dtype=dt, backend=backend,
                               result={"latency_s": float(lat),
                                       "mode": f"{mode}-"
                                               f"{jax.default_backend()}"})
                    if max_err is not None:
                        rec["result"]["max_err_vs_ref"] = max_err
                    records.append(rec)
    return records


def fit_kernel_records(records: Iterable[Dict[str, Any]]
                       ) -> Dict[str, Dict[str, Any]]:
    """Per-(kernel, dtype) least-squares fits from kernel records.

    Returns ``{"<kernel>/<dtype>": {coef, n_points, mean_rel_err, ...,
    phase, backend, max_err_vs_ref}}`` — the dict stored under
    ``CalibrationProfile.kernels``.
    """
    groups: Dict[tuple, List[tuple]] = {}
    errs: Dict[tuple, float] = {}
    for rec in records:
        if rec.get("backend") not in KERNEL_BACKENDS:
            continue
        key = (rec["kernel"], rec.get("dtype", "float32"), rec["phase"],
               rec["backend"])
        res = rec.get("result", {})
        groups.setdefault(key, []).append(
            (float(rec["batch"]), float(rec["tokens"]),
             float(res["latency_s"])))
        if "max_err_vs_ref" in res:
            errs[key] = max(errs.get(key, 0.0),
                            float(res["max_err_vs_ref"]))
    fits: Dict[str, Dict[str, Any]] = {}
    for key, pts in sorted(groups.items()):
        kernel, dtype, phase, backend = key
        fit = fit_phase(pts, phase)
        d = fit.to_dict()
        d.update(phase=phase, backend=backend, kernel=kernel, dtype=dtype)
        if key in errs:
            d["max_err_vs_ref"] = errs[key]
        fits[f"{kernel}/{dtype}"] = d
    return fits


def _measured_int8_compute_scale(repeats: int = 3,
                                 seed: int = 0) -> Optional[float]:
    """Measured dequant overhead: int8 reference matmul vs the same
    shape in plain float32.  Clamped to [1.0, 1.5] so scheduler noise on
    shared CI runners cannot produce an absurd scale."""
    try:
        import jax
        import jax.numpy as jnp
        from repro.kernels import ref
        from repro.serving.latency_model import MeasuredLatency
        (x_q, w_q, sx, sw), _ = _make_int8_matmul(2, 128, "int8", seed)
        x = jnp.asarray(x_q, jnp.float32) * sx[:, None]
        w = jnp.asarray(w_q, jnp.float32) * sw[None, :]
        t_i8 = MeasuredLatency(jax.jit(ref.int8_matmul_reference),
                               warmup=1, iters=repeats,
                               reducer="min").measure(x_q, w_q, sx, sw)
        t_fp = MeasuredLatency(jax.jit(jnp.dot), warmup=1, iters=repeats,
                               reducer="min").measure(x, w)
        if t_fp <= 0:
            return None
        return float(min(max(t_i8 / t_fp, 1.0), 1.5))
    except Exception:
        return None


def derive_speed_modes(kernel_fits: Optional[Dict[str, Dict[str, Any]]]
                       = None, *, measure_compute_scale: bool = False,
                       repeats: int = 3) -> Dict[str, Dict[str, Any]]:
    """Speed-mode parameter dicts for ``CalibrationProfile.speed_modes``.

    Byte scales are exact dtype arithmetic (int8 weights + KV are half
    of bf16) and need no measurement; the int8 ``compute_scale`` —
    quant/dequant overhead — optionally comes from clocking the int8
    reference matmul against plain float32 (CPU proxy; a real TPU run
    refines it from the compiled kernel).  Speculative parameters are
    workload properties, so the conventional defaults ship unless a
    scenario overrides them.
    """
    from repro.serving.latency_model import SPEED_MODES
    modes = {name: mode.to_dict() for name, mode in SPEED_MODES.items()}
    if measure_compute_scale:
        scale = _measured_int8_compute_scale(repeats=repeats)
        if scale is not None:
            modes["int8"]["compute_scale"] = scale
    return modes


def attach_kernel_calibration(profile: CalibrationProfile,
                              records: Iterable[Dict[str, Any]], *,
                              measure_compute_scale: bool = False
                              ) -> CalibrationProfile:
    """Return ``profile`` with kernel fits + derived speed modes merged
    in (existing fields untouched)."""
    fits = fit_kernel_records(records)
    modes = derive_speed_modes(
        fits, measure_compute_scale=measure_compute_scale)
    return dataclasses.replace(profile, kernels=fits or None,
                               speed_modes=modes)
