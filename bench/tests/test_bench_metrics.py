"""The metric readers' arithmetic on synthetic records and traces."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, spec  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.adapter import Batch, Record  # noqa: E402
from bench.generator import Request  # noqa: E402
from bench.measures import DECODE, PREFILL, Run  # noqa: E402

DIMS = {"layers": 2, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
        "ff": 16, "vocab": 32}
PEAKS = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e8}


def rec(due, dispatch, first, done, out, plen=4, tokens=True):
    r = Record(req=Request(rid=0, due=due, prompt=np.zeros(plen, np.int32),
                           out_len=out), dispatch=dispatch,
               first_token=first, done=done)
    r.tokens = np.zeros(out, np.int32) if tokens else None
    return r


@pytest.fixture
def run():
    # ten requests due in a 10 s window, one due after it; the last in the
    # window is answered after the close (drained)
    recs = [rec(due=i, dispatch=i + 0.1 * i, first=i + 0.2 * i + 0.5,
                done=i + 0.2 * i + 2.5, out=1 + i) for i in range(10)]
    recs.append(rec(due=10.5, dispatch=11, first=12, done=13, out=5))
    return Run(family=dense, dims=DIMS, seconds=10.0, setup_s=3.25,
               records=recs, batches=[], peaks=PEAKS)


def read(name, run):
    return spec.reader(name)(run)


def test_ttft_p90_over_every_request_due(run):
    ttft = [0.2 * i + 0.5 for i in range(10)]
    assert read("ttft_p90_s", run) == pytest.approx(np.percentile(ttft, 90))


def test_tpot_p90_skips_single_token_answers(run):
    tpot = [2.0 / i for i in range(1, 10)]
    assert read("tpot_p90_s", run) == pytest.approx(np.percentile(tpot, 90))


def test_output_tokens_completed_in_window(run):
    done = [i for i in range(10) if i + 0.2 * i + 2.5 <= 10.0]
    assert read("output_tok_s", run) == pytest.approx(
        sum(1 + i for i in done) / 10.0)


def test_queue_wait_and_setup(run):
    assert read("queue_wait_p50_s", run) == pytest.approx(
        np.percentile([0.1 * i for i in range(10)], 50))
    assert read("setup_s", run) == 3.25


def traced_run(decode_calls, prefill_calls=1, busy=0.3):
    b = Batch(lengths=[5, 3], outs=[4, 2], dispatch=0.0, traced=True)
    untraced = Batch(lengths=[7], outs=[9], dispatch=1.0)
    trace = {"window_s": 1.0, "busy_s": busy,
             "modules": {DECODE: (decode_calls, 0.03),
                         PREFILL: (prefill_calls, 0.02)}}
    return Run(family=dense, dims=DIMS, seconds=10.0, setup_s=1.0,
               records=[], batches=[b, untraced], peaks=PEAKS, trace=trace)


def test_decode_step_and_roofline():
    run = traced_run(decode_calls=3)
    assert read("decode_step_ms", run) == pytest.approx(10.0)
    # step j serves the rows still owed a token: (5+1, 3+1), (5+2,), (5+3,)
    ctx = [[6, 4], [7], [8]]
    bound = sum(costs.bound_s(dense.decode_flops(DIMS, c),
                              dense.decode_bytes(DIMS, c), PEAKS) for c in ctx)
    assert read("decode_roofline", run) == pytest.approx(100 * bound / 0.03)


def test_prefill_roofline_counts_live_tokens():
    run = traced_run(decode_calls=3)
    assert read("prefill_ms", run) == pytest.approx(20.0)
    f = dense.prefill_flops(DIMS, [5, 3])
    nonembed = 2 * (2 * 8 * 2 * 4 + 2 * 8 * 1 * 4 + 3 * 8 * 16 + 2 * 8) + 8
    assert f == 2 * nonembed * 8 + 4 * 2 * 2 * 4 * (15 + 6) + 2 * 2 * 8 * 32
    bound = costs.bound_s(f, dense.prefill_bytes(DIMS, [5, 3]), PEAKS)
    assert read("prefill_roofline", run) == pytest.approx(100 * bound / 0.02)


def test_step_mfu_and_idle():
    run = traced_run(decode_calls=3)
    flops = dense.prefill_flops(DIMS, [5, 3]) + sum(
        dense.decode_flops(DIMS, c) for c in ([6, 4], [7], [8]))
    want = 100 * flops / (0.05 * PEAKS["bf16_flops"])
    assert read("step_mfu.chat", run) == pytest.approx(want)
    assert read("step_mfu.long", run) == pytest.approx(want)
    assert read("idle_share", run) == pytest.approx(70.0)


def test_a_trace_that_disagrees_reads_nothing(capsys):
    run = traced_run(decode_calls=4)
    assert read("decode_step_ms", run) is None
    assert "jit_serve_decode has (4, 0.03)" in capsys.readouterr().err
    assert read("decode_roofline", run) is None
    assert read("step_mfu.chat", run) is None
    del run.trace["modules"][PREFILL]
    assert read("prefill_ms", run) is None
    assert "jit_serve_prefill has None" in capsys.readouterr().err
    run.trace = None
    assert read("prefill_roofline", run) is None
    assert read("idle_share", run) is None
