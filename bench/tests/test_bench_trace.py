"""The trace reduction gives known numbers on a small recorded trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from jax.profiler import ProfileData  # noqa: E402

from bench import trace_reduce  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.textproto"


@pytest.fixture(scope="module")
def summary():
    text = FIXTURE.read_text()
    return trace_reduce.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def test_window_and_busy(summary):
    assert summary["window_s"] == pytest.approx(10e-6)
    assert summary["busy_s"] == pytest.approx(3.9e-6)


def test_module_time(summary):
    calls, t = summary["modules"]["jit_serve_prefill"]
    assert (calls, t) == (1, pytest.approx(2.0e-6))
    calls, t = summary["modules"]["jit_serve_decode"]
    assert (calls, t) == (2, pytest.approx(2.0e-6))
    assert trace_reduce.module(summary, "jit_serve_decode")[0] == 2
    assert trace_reduce.module(None, "jit_serve_decode") is None


def test_device_ops_by_time(summary):
    names = [n for n, _ in summary["device_ops"]]
    assert names == ["fusion.1", "dot.2"]
    assert [t for _, t in summary["device_ops"]] == pytest.approx(
        [2.5e-6, 1.4e-6])


def test_idle_gaps_named_by_open_span(summary):
    gaps = dict(summary["idle_gaps"])
    assert gaps == pytest.approx({"bench.wait": 4.6e-6,
                                  "bench.decode": 1.0e-6,
                                  "bench.prefill": 0.5e-6})
    assert [n for n, _ in summary["idle_gaps"]][0] == "bench.wait"


def test_no_device_gives_nothing():
    text = FIXTURE.read_text().split("planes {\n  id: 2")[0]
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert trace_reduce.reduce(pd) is None
