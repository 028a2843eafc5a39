"""Speed modes (int8 / speculative): mode algebra, oracle scaling,
memory-budget interaction, kernel-calibration plumbing, and the planner's
speed-mode axis (the quantized config must win KV-bound and lose
compute-bound)."""
import math

import pytest

from repro import hw as hw_lib
from repro.calibrate import (attach_kernel_calibration, derive_speed_modes,
                             fit_kernel_records, kernel_records,
                             kernel_registry, plan_capacity,
                             run_calibration_job, simulate_candidate)
from repro.calibrate.profile import CalibrationProfile
from repro.configs import get_config
from repro.core.spec import CalibrationSpec, ModelRef, PlanSpec
from repro.serving.cluster import ClusterSpec, simulate_cluster
from repro.serving.latency_model import (SPEED_MODES, FittedLatencyModel,
                                         LatencyModel, SpeedMode,
                                         apply_speed_mode,
                                         resolve_speed_mode)
from repro.serving.memory import MemorySpec, resolve_memory, scaled_memory_spec
from repro.serving.batching import ContinuousBatcher
from repro.serving.workload import WorkloadSpec

HW = hw_lib.HARDWARE["tpu-v5e"]


def roofline(chips=1, **kw):
    return LatencyModel(get_config("gemma2-2b"), hw=HW, chips=chips, **kw)


def fitted(**kw):
    return FittedLatencyModel(prefill_coef=(2e-3, 5e-6, 1.5e-8),
                              decode_coef=(1e-3, 2e-4, 3e-7), chips=1, **kw)


# ---- mode algebra -----------------------------------------------------------
def test_presets_and_resolution():
    assert set(SPEED_MODES) == {"fp16", "int8", "speculative"}
    assert resolve_speed_mode(None).is_identity
    assert resolve_speed_mode("fp16").is_identity
    int8 = resolve_speed_mode("int8")
    assert int8.kv_bytes_scale == 0.5 and int8.weight_bytes_scale == 0.5
    # dict / SpeedMode / override resolution
    custom = SpeedMode("int8", kv_bytes_scale=0.25)
    assert resolve_speed_mode(custom) is custom
    assert resolve_speed_mode({"name": "x", "compute_scale": 2.0}
                              ).compute_scale == 2.0
    got = resolve_speed_mode("int8", {"int8": custom.to_dict()})
    assert got.kv_bytes_scale == 0.25
    with pytest.raises(KeyError):
        resolve_speed_mode("fp4")
    with pytest.raises(TypeError):
        resolve_speed_mode(3.14)


def test_mode_round_trip_and_validation():
    mode = SpeedMode("spec", draft_len=4, acceptance_rate=0.7,
                     draft_cost_frac=0.3)
    assert SpeedMode.from_dict(mode.to_dict()) == mode
    with pytest.raises(ValueError):
        SpeedMode("bad", acceptance_rate=1.5)
    with pytest.raises(ValueError):
        SpeedMode("bad", kv_bytes_scale=0.0)
    with pytest.raises(ValueError):
        SpeedMode("bad", draft_len=-1)


def test_expected_tokens_and_cost_factor():
    vanilla = SpeedMode("fp16")
    assert vanilla.decode_cost_factor() == 1.0
    spec = SpeedMode("s", draft_len=4, acceptance_rate=1.0,
                     draft_cost_frac=1.0)
    # perfect acceptance at full draft cost: k+1 tokens for (1 + k) cost
    assert spec.expected_tokens_per_cycle() == pytest.approx(5.0)
    assert spec.decode_cost_factor() == pytest.approx(1.0)
    free = SpeedMode("s", draft_len=4, acceptance_rate=1.0,
                     draft_cost_frac=0.0)
    assert free.decode_cost_factor() == pytest.approx(1.0 / 5.0)
    # factor is strictly decreasing in acceptance rate
    factors = [SpeedMode("s", draft_len=4, acceptance_rate=a,
                         draft_cost_frac=0.3).decode_cost_factor()
               for a in (0.0, 0.25, 0.5, 0.75, 1.0)]
    assert all(a > b for a, b in zip(factors, factors[1:]))


# ---- oracle scaling ---------------------------------------------------------
def test_fp16_is_identity_passthrough():
    base = roofline()
    assert apply_speed_mode(base, "fp16") is base
    assert apply_speed_mode(base, None) is base


def test_speculative_unit_acceptance_reduces_to_vanilla_tpot():
    """acceptance=1.0 at draft_cost_frac=1.0 must reproduce vanilla decode
    *exactly* — bit-for-bit, not approximately."""
    unit = SpeedMode("spec1", draft_len=4, acceptance_rate=1.0,
                     draft_cost_frac=1.0)
    for base in (roofline(chips=4), fitted()):
        spec = apply_speed_mode(base, unit)
        for b, c in ((1, 128), (8, 1024), (32, 4096)):
            assert spec.decode_latency(b, c) == base.decode_latency(b, c)
            assert spec.prefill_latency(b, c) == base.prefill_latency(b, c)


def test_draft_len_zero_is_identity():
    mode = SpeedMode("noop", draft_len=0, acceptance_rate=0.9)
    assert mode.is_identity
    base = roofline()
    assert apply_speed_mode(base, mode) is base


def test_int8_halves_memory_footprint_and_speeds_memory_bound_decode():
    base = roofline(chips=4)
    int8 = apply_speed_mode(base, "int8")
    assert int8.kv_bytes_per_token() == pytest.approx(
        base.kv_bytes_per_token() / 2)
    assert int8.weight_bytes() == pytest.approx(base.weight_bytes() / 2)
    # decode at small batch is weight-read bound: halving bytes must help
    assert int8.decode_latency(1, 1024) < base.decode_latency(1, 1024)


def test_fitted_mode_mapping_scales_the_right_coefficients():
    base = fitted()
    int8 = base.with_speed_mode(resolve_speed_mode("int8"))
    p0, p1, p2 = base.prefill_coef
    d0, a, bta = base.decode_coef
    cs = 1.05
    assert int8.prefill_coef == pytest.approx((p0, p1 * cs, p2 * cs))
    assert int8.decode_coef == pytest.approx((d0 * 0.5, a * cs, bta * 0.5))
    assert int8.name.endswith("+int8")


def test_generic_wrapper_hides_absent_memory_hooks():
    """Oracles without kv_bytes_per_token must stay hook-less after
    wrapping, so memory resolution keeps treating them as profile-like.
    Without a roofline split the wrapper is conservative: int8 decode
    scales by compute_scale (never optimistically by the byte scale),
    while speculative decoding still pays off through the cost factor."""
    class Plain:
        def prefill_latency(self, b, s):
            return 1e-3 * b

        def decode_latency(self, b, c):
            return 1e-4 * b

    wrapped = apply_speed_mode(Plain(), "int8")
    assert getattr(wrapped, "kv_bytes_per_token", None) is None
    assert wrapped.prefill_latency(2, 64) == pytest.approx(2e-3 * 1.05)
    assert wrapped.decode_latency(2, 64) == pytest.approx(2e-4 * 1.05)
    free = SpeedMode("s", draft_len=4, acceptance_rate=1.0,
                     draft_cost_frac=0.0)
    spec = apply_speed_mode(Plain(), free)
    assert spec.decode_latency(2, 64) == pytest.approx(2e-4 / 5.0)


# ---- memory invariant -------------------------------------------------------
def test_int8_strictly_increases_max_feasible_batch():
    """Under a fixed HBM budget, int8's half-size KV entries must admit a
    strictly larger max feasible batch at every context length."""
    base = roofline()
    spec = MemorySpec(hbm_gb=2.0)
    fp16_mem = resolve_memory(spec, base)
    int8_mode = resolve_speed_mode("int8")
    int8_mem = resolve_memory(scaled_memory_spec(spec, int8_mode) or spec,
                              apply_speed_mode(base, int8_mode))
    assert int8_mem.total_blocks > fp16_mem.total_blocks
    for ctx in (512, 2048, 8192):
        tokens_per_req = ctx
        fp16_batch = fp16_mem.total_blocks * spec.block_tokens \
            // tokens_per_req
        int8_batch = int8_mem.total_blocks * spec.block_tokens \
            // tokens_per_req
        assert int8_batch > fp16_batch


def test_scaled_memory_spec_only_rescales_explicit_bytes():
    int8 = resolve_speed_mode("int8")
    assert scaled_memory_spec(None, int8) is None
    derived = MemorySpec(hbm_gb=2.0)      # kv bytes derived from oracle
    assert scaled_memory_spec(derived, int8) is derived
    explicit = MemorySpec(hbm_gb=2.0, kv_bytes_per_token=4096.0)
    scaled = scaled_memory_spec(explicit, int8)
    assert scaled.kv_bytes_per_token == pytest.approx(2048.0)


# ---- goodput monotonicity ---------------------------------------------------
def test_acceptance_rate_sweep_is_monotone_in_goodput():
    """Higher draft acceptance → cheaper effective decode → goodput under
    a TPOT SLO must be non-decreasing, and strictly better end-to-end."""
    base = roofline(chips=4)
    wl = WorkloadSpec(rate=6.0, duration_s=12.0, prompt_tokens=256,
                      output_tokens=128)
    rates = (0.2, 0.6, 1.0)
    # SLO pinned between the slowest and fastest mode's decode cost so
    # the sweep actually separates: mid-acceptance TPOT at a busy batch
    mid = apply_speed_mode(base, SpeedMode("s", draft_len=4,
                                           acceptance_rate=rates[1],
                                           draft_cost_frac=0.3))
    tpot_slo = mid.decode_latency(8, 384) * 1.05
    goodputs = []
    for a in rates:
        mode = SpeedMode(f"spec{a}", draft_len=4, acceptance_rate=a,
                         draft_cost_frac=0.3)
        oracle = apply_speed_mode(base, mode)
        res = simulate_cluster(wl, ContinuousBatcher(max_batch=8), oracle,
                               cluster=ClusterSpec(replicas=1))
        goodputs.append(res.goodput(tpot_slo_s=tpot_slo))
    assert all(g1 <= g2 + 1e-9 for g1, g2 in zip(goodputs, goodputs[1:]))
    assert goodputs[-1] > goodputs[0]


# ---- planner axis -----------------------------------------------------------
KV_BOUND = WorkloadSpec(rate=4.0, duration_s=15.0, prompt_tokens=2048,
                        output_tokens=256)


def test_planner_int8_wins_kv_bound():
    """Long contexts + tight HBM: fp16 can't fit the big batch, int8 can —
    the quantized config must win on cost-per-goodput, and its claimed
    attainment must survive an independent re-simulation."""
    base = roofline()
    mem = MemorySpec(hbm_gb=2.0)
    plan = plan_capacity(base, KV_BOUND, slo_latency_s=20.0, slo_target=0.9,
                         replicas=(1,), policies=("continuous",),
                         max_batches=(8, 16),
                         speed_modes=["fp16", "int8", "speculative"],
                         memory=mem, objective="cost_per_goodput")
    modes = {c.speed_mode for c in plan.candidates}
    assert modes == {"fp16", "int8", "speculative"}
    best = plan.best
    assert best is not None and best.speed_mode == "int8"
    # fp16 is memory-rejected exactly where int8 fits
    rejected = {(c.speed_mode, c.max_batch)
                for c in plan.candidates if c.infeasible_reason}
    assert ("fp16", 16) in rejected
    assert ("int8", 16) not in rejected
    # verify half of plan → verify: replay the winner independently
    res = simulate_candidate(base, KV_BOUND, best, memory=mem)
    assert res.slo_attainment(20.0) >= 0.9


def test_planner_fp16_wins_compute_bound():
    """Prefill is compute-bound, so int8's 5% compute tax makes every
    TTFT strictly worse.  Pin the TTFT SLO between the two modes'
    observed worst cases (same seeded workload the planner replays):
    fp16 keeps full goodput, int8 drops requests — the vanilla config
    must win on cost-per-goodput."""
    base = roofline(chips=4)
    # sparse single-token requests: no decode phase and no queueing, so
    # TTFT is pure network + prefill and the 5% compute tax separates
    # the modes cleanly
    wl = WorkloadSpec(rate=0.5, duration_s=20.0, prompt_tokens=512,
                      output_tokens=1)
    cluster = ClusterSpec(replicas=1)
    maxima = []
    for name in ("fp16", "int8"):
        oracle = apply_speed_mode(base, name)
        res = simulate_cluster(wl, ContinuousBatcher(max_batch=4), oracle,
                               cluster=cluster)
        maxima.append(res.ttft(100.0))
    assert maxima[1] > maxima[0]      # int8 prefill is strictly slower
    ttft_slo = (maxima[0] + maxima[1]) / 2
    plan = plan_capacity(base, wl, ttft_slo_s=ttft_slo, slo_target=0.9,
                         replicas=(1,), policies=("continuous",),
                         max_batches=(4,), speed_modes=["fp16", "int8"],
                         objective="cost_per_goodput")
    best = plan.best
    assert best is not None and best.speed_mode == "fp16"
    by_mode = {c.speed_mode: c for c in plan.candidates}
    assert by_mode["fp16"].objective < by_mode["int8"].objective


def test_simulate_candidate_honors_speed_mode():
    base = roofline()
    mem = MemorySpec(hbm_gb=2.0)
    plan = plan_capacity(base, KV_BOUND, slo_latency_s=20.0, slo_target=0.9,
                         replicas=(1,), policies=("continuous",),
                         max_batches=(8,), speed_modes=["fp16", "int8"],
                         memory=mem, objective="cost_per_goodput")
    by_mode = {c.speed_mode: c for c in plan.candidates
               if not c.infeasible_reason}
    res_fp16 = simulate_candidate(base, KV_BOUND, by_mode["fp16"],
                                  memory=mem)
    res_int8 = simulate_candidate(base, KV_BOUND, by_mode["int8"],
                                  memory=mem)
    assert res_int8.percentile(99) < res_fp16.percentile(99)


def test_plan_spec_round_trips_speed_modes():
    spec = PlanSpec(job_id="p", user="t", profile="gemma2-2b@tpu-v5e",
                    speed_modes=("fp16", "int8"))
    spec2 = PlanSpec.from_dict(spec.to_dict())
    assert tuple(spec2.speed_modes) == ("fp16", "int8")


# ---- kernel calibration backend ---------------------------------------------
def test_kernel_registry_names():
    assert set(kernel_registry()) == {"flash_attention", "decode_attention",
                                      "int8_matmul", "wkv6", "rglru_scan"}


def test_kernel_records_provenance_and_fit():
    recs = kernel_records(["wkv6"], batches=(1, 2), seqs=(64, 128),
                          dtypes=("float32",), repeats=1,
                          meta={"job_id": "k"})
    assert len(recs) == 4
    for r in recs:
        assert r["kind"] == "calibration"
        # the suite runs on the CPU, where the kernels are interpreted
        assert r["backend"] == "pallas-interpret"
        assert r["kernel"] == "wkv6"
        assert r["result"]["latency_s"] > 0
        assert r["result"]["max_err_vs_ref"] is not None
    fits = fit_kernel_records(recs)
    assert set(fits) == {"wkv6/float32"}
    fit = fits["wkv6/float32"]
    assert fit["backend"] == "pallas-interpret"
    assert fit["n_points"] == 4


def test_attach_kernel_calibration_and_profile_round_trip():
    prof = roofline().to_profile()
    recs = kernel_records(["rglru_scan"], batches=(1,), seqs=(64,),
                          dtypes=("float32",), repeats=1)
    prof = attach_kernel_calibration(prof, recs)
    assert prof.kernels and "rglru_scan/float32" in prof.kernels
    assert set(prof.speed_modes) == {"fp16", "int8", "speculative"}
    prof2 = CalibrationProfile.from_dict(prof.to_dict())
    assert prof2.kernels == prof.kernels
    assert prof2.speed_modes == prof.speed_modes
    # profile-carried speed modes override the built-in presets
    custom = dict(prof2.speed_modes)
    custom["int8"] = dict(custom["int8"], kv_bytes_scale=0.25)
    assert resolve_speed_mode("int8", custom).kv_bytes_scale == 0.25


def test_run_calibration_job_with_kernels(tmp_path):
    spec = CalibrationSpec(
        job_id="k", user="t",
        model=ModelRef(kind="registered", name="gemma2-2b"),
        hardware="tpu-v5e", chips=1, batches=(1,), seqs=(64,), repeats=1,
        kernels=("int8_matmul",), profile_dir=str(tmp_path))
    res = run_calibration_job(spec)
    assert res.metrics["kernels"] == ["int8_matmul"]
    assert res.metrics["n_kernel_records"] >= 1
    krecs = [r for r in res.extra_records
             if r.get("backend") == "pallas-interpret"]
    assert krecs and all(r["kind"] == "calibration" for r in krecs)
    prof = CalibrationProfile.from_dict(res.metrics["profile"])
    assert prof.kernels and prof.speed_modes


def test_derive_speed_modes_shape():
    modes = derive_speed_modes()
    assert set(modes) == {"fp16", "int8", "speculative"}
    for d in modes.values():
        SpeedMode.from_dict(d)   # every derived mode must round-trip
