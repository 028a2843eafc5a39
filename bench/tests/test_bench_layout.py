"""Cells, traffic mixes, metrics and families are found by name from files
of their own: adding one is adding files and entries, with no edit to a
file that is there.  And the command refuses to run without a TPU."""
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
# A second family's files (``bench/<part>/...``) and its BENCHMARK.json
# entries: the program's top-k MoE at a small size, with a plain reference.
SECOND = Path(__file__).parent / "fixtures" / "second_family"

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


# Run from the copy, so that ``bench`` is the copy's: builds and serves the
# second family's cell, checks it against its reference, and reads its
# metric from the scoped fixture trace with the MLP's scope renamed to the
# family's ``moe``.
DRIVE = """
import json
from jax.profiler import ProfileData
from bench import cellrun, check, scopes, spec
from bench.adapter import Batch
from bench.measures import Run

cell = spec.load("granite-moe-toy.tiny")
seed = 2**33 + 21
server = cellrun.build(cell, seed)
served = cellrun.serve(server, cell, seed, 2.0)
picked = check.sample(served.records, seed,
                      cell.params["check"]["served_tokens"])
gaps = check.served_gaps(cell, seed, picked, control=True)
text = (spec.BENCH / "tests" / "fixtures" / "scoped_trace.textproto"
        ).read_text().replace("/closed_call/mlp/", "/closed_call/moe/")
raw = ProfileData.text_proto_to_serialized_xspace(text)

def reading(family):
    names = scopes.BASE + spec.family(family).SCOPES
    run = Run(family=cell.family, dims=cell.dims, seconds=1.0, setup_s=0.0,
              records=[], peaks={}, trace=scopes.summarize(raw, names),
              batches=[Batch(lengths=[3, 2], outs=[3, 2], dispatch=0.0,
                             traced=True)])
    return spec.reader("prefill_moe_ms")(run)

print(json.dumps({
    "modules": [str(spec.BENCH), cell.family.__file__,
                check.reference(cell.config["family"]).__file__],
    "experts": server.params["layers"]["ffn"]["wi"].shape[1],
    "attempted": served.attempted, "unanswered": served.unanswered,
    "compiles": served.compiles,
    "checked": sum(r.req.out_len for r in picked),
    "gap": gaps["served"], "control": gaps["control"],
    "limit": cell.params["check"]["max_logit_gap"],
    "moe_ms": reading("moe"), "moe_ms_by_dense_names": reading("dense")}))
"""


def test_a_second_family_needs_no_edit(copy):
    before = digest(copy)
    for part in ("families", "reference", "configs", "workloads", "traffic",
                 "metrics"):
        shutil.copytree(SECOND / part, copy / "bench" / part,
                        dirs_exist_ok=True)
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    for key, entries in json.loads((SECOND / "entries.json").read_text()
                                   ).items():
        bench[key] += entries
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(copy), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", DRIVE], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    bench_dir = copy / "bench"
    assert got["modules"] == [str(bench_dir),
                              str(bench_dir / "families" / "moe.py"),
                              str(bench_dir / "reference" / "moe.py")]
    assert got["experts"] == 4
    assert got["attempted"] > 10 and got["unanswered"] == 0
    assert got["compiles"] == 0 and got["checked"] >= 64
    assert got["gap"] <= got["limit"] < got["control"]
    # 0.5 us of the prefill's self time lies under the family's scope; the
    # dense family lists no such scope, so there the op is unscoped
    assert got["moe_ms"] == pytest.approx(0.5e-3)
    assert got["moe_ms_by_dense_names"] == 0.0
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
