"""Per-scope self time of the served steps, and the metrics that read it,
on a small hand-written trace (``fixtures/scoped_trace.textproto``) and on
a trace recorded on the chip (``fixtures/recorded_chat.xplane.pb``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from jax.profiler import ProfileData  # noqa: E402

from bench import scopes, trace_reduce  # noqa: E402
from bench.adapter import Batch  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.measures import DECODE, PREFILL, Run  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "scoped_trace.textproto"
# Recorded on a TPU v5 lite by ``bench/scoped.py --keep`` in a traced
# window of granite-3-2b.chat (seed 2147491567), then cut down to the first
# prefill (batch 8) and the three decode calls after it: the device's
# ``XLA Modules`` and ``XLA Ops`` events, the ``bench.*`` spans of that
# stretch (the open ``bench.decode`` cut at the third decode's end), and of
# each event's metadata only its name and ``tf_op`` stat.
RECORDED = Path(__file__).parent / "fixtures" / "recorded_chat.xplane.pb"
DENSE = {"embed", "attn_qkv", "kv_write", "attn_core", "attn_out", "mlp",
         "logits", "sample"}
NAMES = scopes.BASE + dense.SCOPES
US = 1e-6
DIMS = {"layers": 2, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
        "ff": 16, "vocab": 32}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e10}


def serialized(text: str) -> bytes:
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_serialized_xspace(serialized(FIXTURE.read_text()))


@pytest.fixture(scope="module")
def red():
    return scopes.summarize(serialized(FIXTURE.read_text()), NAMES)


def traced_run(red, outs=(3, 2)):
    """One traced batch of two prompts (3 and 2 tokens): one prefill and
    max(outs) - 1 decode calls."""
    return Run(family=dense, dims=DIMS, seconds=1.0, setup_s=0.0,
               records=[],
               batches=[Batch(lengths=[3, 2], outs=list(outs), dispatch=0.0,
                              traced=True)],
               peaks=PEAKS, trace=dict(red))


@pytest.mark.parametrize("path,scope", [
    ("jit(serve_decode)/while/body/closed_call/attn_core/jit(_where)/select_n",
     "attn_core"),
    ("jit(serve_decode)/logits/sample/reduce", "sample"),
    ("jit(serve_decode)/logits/sample", "sample"),
    ("jit(serve_prefill)/while/body/closed_call/mlp/bsd,df->bsf/dot_general",
     "mlp"),
    ("jit(serve_decode)/while/body/dynamic_update_slice", "unscoped"),
    ("", "unscoped"),
    ("jit(serve_decode)/attn_core_extra/dot_general", "unscoped"),
])
def test_scope_is_the_innermost_known_component(path, scope):
    assert scopes.scope_of(path, NAMES) == scope


def test_self_time_leaves_a_container_what_its_body_does_not_cover():
    # a while over two body ops, one nested one level deeper, then a
    # sibling after the while
    ops = [(0, 10), (1, 4), (2, 3), (5, 7), (10, 12)]
    assert scopes.self_times(ops) == [10 - 3 - 2, 3 - 1, 1, 2, 2]
    assert sum(scopes.self_times(ops)) == 12


def test_self_times_by_scope(red):
    assert red["scopes"][PREFILL] == pytest.approx(
        {"unscoped": 0.6 * US, "attn_core": 0.5 * US, "mlp": 0.5 * US,
         "logits": 0.3 * US})
    assert red["scopes"][DECODE] == pytest.approx(
        {"unscoped": 0.8 * US, "attn_core": 0.4 * US, "kv_write": 0.2 * US,
         "sample": 0.2 * US})


def test_self_times_add_up_to_busy_time_and_the_module_adds_its_gaps(pd, red):
    modules = trace_reduce.reduce(pd)["modules"]
    assert red["step_busy_s"] == pytest.approx({PREFILL: 1.9 * US, DECODE: 1.6 * US})
    for step, busy in red["step_busy_s"].items():
        assert sum(red["scopes"][step].values()) == pytest.approx(busy)
        assert busy <= modules[step][1]
    assert modules[PREFILL][1] - red["step_busy_s"][PREFILL] == pytest.approx(0.1 * US)


def test_ops_outside_the_served_steps_or_the_window_do_not_count(red):
    assert set(red["scopes"]) == {PREFILL, DECODE}
    assert "fusion.11" not in red["scope_ops"][PREFILL]
    assert "fusion.11" not in red["scope_ops"][DECODE]
    # the decode at 8.5 us, after the window, adds nothing
    assert red["scope_ops"][DECODE]["fusion.7"] == ["attn_core", pytest.approx(0.4 * US)]


def test_ops_keep_their_scope(red):
    assert red["scope_ops"][DECODE]["copy.10"] == ["unscoped", pytest.approx(0.2 * US)]
    assert red["scope_ops"][PREFILL]["while.1"] == ["unscoped", pytest.approx(0.3 * US)]


def test_no_device_or_no_span_gives_nothing():
    text = FIXTURE.read_text()
    assert scopes.summarize(serialized("planes {" + text.split("planes {")[2]),
                            NAMES) is None
    assert scopes.summarize(serialized(text.split("planes {\n  id: 2")[0]),
                            NAMES) is None


def test_metrics_per_call(red):
    run = traced_run(red)
    ms = 1e3 * US
    read = {k: f(run) for k, f in scopes.READERS.items()}
    assert read["decode_attn_ms"] == pytest.approx(0.2 * ms)
    assert read["decode_kv_ms"] == pytest.approx(0.1 * ms)
    assert read["decode_unscoped_ms"] == pytest.approx(0.4 * ms)
    assert read["prefill_attn_ms"] == pytest.approx(0.5 * ms)
    assert read["prefill_logits_ms"] == pytest.approx(0.3 * ms)


def test_attention_rooflines(red):
    run = traced_run(red)
    # decode calls attend over 4 + 3 and then 5 live entries; per entry
    # 4 x 2 layers x 2 heads x 4 = 64 operations and 2 x 2 x 1 x 4 x 2 =
    # 32 bytes, so bytes bound both calls: (7 + 5) x 32 / 1e10 s
    assert scopes.decode_attn_roofline(run) == pytest.approx(
        100 * 12 * 32 / 1e10 / (0.4 * US))
    # prefill: 3 x 4 / 2 + 2 x 3 / 2 = 9 causal pairs at 64 operations
    assert scopes.prefill_attn_roofline(run) == pytest.approx(
        100 * 9 * 64 / 1e12 / (0.5 * US))


def test_metrics_are_none_where_calls_disagree_or_scopes_are_missing(red):
    run = traced_run(red, outs=(4, 2))      # three decode calls made
    assert all(scopes.READERS[k](run) is None
               for k in scopes.READERS if k.startswith("decode"))
    assert scopes.READERS["prefill_attn_ms"](run) == pytest.approx(0.5e-3)
    run = traced_run(red)
    del run.trace["scopes"]
    assert all(f(run) is None for f in scopes.READERS.values())
    run.trace = None
    assert all(f(run) is None for f in scopes.READERS.values())


def test_breakdown_names_each_op_by_step_and_scope(red):
    from bench import scoped
    got = scoped.breakdown(red)
    # values and order are trace_reduce's; names gain step and scope
    assert got["device_ops"][:2] == [
        ["prefill/unscoped|while.1", pytest.approx(1.6 * US)],
        ["decode/unscoped|while.6", pytest.approx(1.2 * US)]]
    names = dict(got["device_ops"])
    assert names["decode/attn_core|fusion.7"] == pytest.approx(0.4 * US)
    assert names["prefill/logits|fusion.5"] == pytest.approx(0.3 * US)
    dec = got["steps"][DECODE]
    assert (dec["calls"], dec["self_s"]) == (2, pytest.approx(dec["busy_s"]))
    assert dec["scopes"][0] == ("unscoped", pytest.approx(0.8 * US))
    assert [o for o, _ in dec["unscoped_ops"]] == ["while.6", "copy.10"]


@pytest.fixture(scope="module")
def recorded():
    raw = RECORDED.read_bytes()
    return ProfileData.from_serialized_xspace(raw), scopes.summarize(raw, NAMES)


def test_recorded_trace_has_the_planes_lines_and_modules_assumed(recorded):
    pd, _ = recorded
    device = {pl.name: {ln.name for ln in pl.lines} for pl in pd.planes
              if pl.name.startswith(trace_reduce.DEVICE_PREFIX)}
    assert device == {"/device:TPU:0": {trace_reduce.MODULES, trace_reduce.OPS}}
    summary = trace_reduce.reduce(pd)
    assert {k: n for k, (n, _) in summary["modules"].items()} == {
        PREFILL: 1, DECODE: 3}
    assert summary["busy_s"] <= summary["window_s"]
    assert {n for n, _ in summary["idle_gaps"]} <= {
        "bench.inputs", "bench.prefill", "bench.sync", "bench.decode"}


def test_recorded_trace_names_every_dense_scope(recorded):
    _, red = recorded
    found = set(red["scopes"][PREFILL]) | set(red["scopes"][DECODE])
    assert found == DENSE | {scopes.UNSCOPED}
    # XLA fuses the prompt's cache write into the scan's stacking of the
    # layer caches (unscoped) and decode's argmax into the logits head
    assert DENSE - set(red["scopes"][PREFILL]) == {"kv_write"}
    assert DENSE - set(red["scopes"][DECODE]) == {"sample"}


def test_recorded_self_times_add_up_to_each_steps_device_time(recorded):
    pd, red = recorded
    modules = trace_reduce.reduce(pd)["modules"]
    for step in (PREFILL, DECODE):
        total = sum(red["scopes"][step].values())
        assert total == pytest.approx(red["step_busy_s"][step], rel=1e-9)
        assert total == pytest.approx(modules[step][1], rel=1e-3)
    assert red["scopes"][PREFILL]["attn_core"] > red["scopes"][PREFILL]["logits"]
