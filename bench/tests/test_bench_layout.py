"""Cells, traffic mixes and metrics are found by name from files of their
own: adding one is adding files and entries, with no edit to a file that
is there.  And the command refuses to run without a TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generator, spec  # noqa: E402


def digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def copy(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_every_committed_cell_and_metric_is_found():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_new_cell_mix_and_metric_need_no_edit(copy):
    before = digest(copy)
    (copy / "bench" / "traffic" / "burst.json").write_text(json.dumps({
        "arrivals": "poisson", "block": 32,
        "prompt": {"median": 64, "sigma": 0.5, "min": 16, "max": 128},
        "output": {"median": 16, "sigma": 0.5, "min": 4, "max": 32}}))
    (copy / "bench" / "workloads" / "granite-3-2b.burst.json").write_text(
        json.dumps({"rate": 9.0, "trace_span_s": 3,
                    "batcher": {"policy": "tris", "preferred": [2, 1]},
                    "check": {"served_tokens": 64, "max_logit_gap": 1.0}}))
    (copy / "bench" / "metrics" / "mean_batch.py").write_text(
        "def read(run):\n"
        "    return sum(len(b.lengths) for b in run.batches) / len(run.batches)\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "granite-3-2b.burst",
                               "config": "granite-3-2b", "traffic": "burst",
                               "chips": 1, "why": "bursts"})
    bench["per_layer"].append({"name": "mean_batch", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "batcher (serving.batching)",
                               "moves": "ttft_p90_s",
                               "workloads": ["granite-3-2b.burst"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load("granite-3-2b.burst", root=copy)
    assert cell.pad == 128 and cell.max_len == 160
    assert [m["name"] for m in cell.per_layer] == ["mean_batch"]
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    reqs = generator.open_loop(cell.mix, 3, cell.dims["vocab"],
                               cell.params["rate"], 10.0)
    assert all(16 <= len(r.prompt) <= 128 for r in reqs)

    class FakeRun:
        batches = [type("B", (), {"lengths": [1, 2]})()] * 3
    assert spec.reader("mean_batch", root=copy)(FakeRun()) == 2.0
    after = digest(copy)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "granite-3-2b.chat", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
