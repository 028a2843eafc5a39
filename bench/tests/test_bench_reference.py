"""The plain reference against the program's prefill and cached decode,
and the check that decides ``correct`` against planted faults, all at a
small size on the CPU."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import adapter, cellrun, check, spec, weights  # noqa: E402
from bench.reference import dense  # noqa: E402

SMALL = ["num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size"]


def small_config(dtype):
    return {"name": "small", "catalog": "granite-3-2b", "family": "dense",
            "num_hidden_layers": 2, "hidden_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
            "dtype": dtype, "reduced": SMALL}


def small_cell(dtype="bfloat16", limit=0.015):
    mix = {"arrivals": "poisson", "block": 16,
           "prompt": {"median": 16, "sigma": 0.8, "min": 4, "max": 32},
           "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 16}}
    params = {"rate": 40, "batcher": {"policy": "tris", "preferred": [4, 2, 1]},
              "check": {"served_tokens": 96, "max_logit_gap": limit},
              "trace_span_s": 1}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "granite-3-2b.chat"
    return spec.Cell(name="small", entry={"chips": 1}, params=params,
                     config=small_config(dtype), mix=mix,
                     end_to_end=[m for m in bench["end_to_end"]
                                 if name in m.get("workloads", [name])],
                     per_layer=[])


def test_reference_matches_prefill_and_cached_decode():
    cell = small_cell("float32")
    seed = 2**33 + 5
    server = adapter.Server(adapter.model_config(cell.config),
                            cellrun.new_params(cell, seed), pad=24, max_len=40)
    rng = np.random.default_rng(0)
    lens = np.array([24, 17, 9])
    toks = np.zeros((3, 24), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, 512, n)
    model = adapter.build_model(
        adapter.serving_config(adapter.model_config(cell.config)))
    prefill = jax.jit(adapter.make_prefill_fn(model, max_len=40))
    decode = jax.jit(adapter.make_decode_fn(model))
    cache, logits = prefill(server.params, jnp.asarray(toks), jnp.asarray(lens))
    got, seqs = [logits], [list(t[:n]) for t, n in zip(toks, lens)]
    for _ in range(6):
        nxt = jnp.argmax(got[-1], -1).astype(jnp.int32)
        for s, t in zip(seqs, np.asarray(nxt)):
            s.append(int(t))
        cache, logits = decode(server.params, cache, nxt)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)          # (3, 7, V)
    rows = np.zeros((3, 40), np.int32)
    for i, s in enumerate(seqs):
        rows[i, :len(s)] = s
    pos = np.stack([n - 1 + np.arange(7) for n in lens]).astype(np.int32)
    want = np.asarray(dense.logits_at(seed, cell.dims, "float32", [rows],
                                      [pos])[0])
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_reference_makes_the_programs_weights_again():
    cell = small_cell("bfloat16")
    p = cellrun.new_params(cell, 77)
    key = tuple(sorted(cell.dims.items()))
    w = dense._layer_weights(weights.seed_words(77), np.int32(1), key,
                             "bfloat16")
    assert jnp.array_equal(w["wk"], p["layers"]["attn"]["wk"][1])
    assert jnp.array_equal(w["w_gate"], p["layers"]["ffn"]["wg"][1])


def run_small(cell, seed):
    import importlib.util
    s = importlib.util.spec_from_file_location("bench_run_main",
                                               ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    return run.run_cell(cell, seed, 2.0, False,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                        {"platform": "cpu", "kind": "cpu", "count": 1})


def test_a_sound_run_is_correct():
    out = run_small(small_cell(), 2**31 + 3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 20
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_s", "tpot_p90_s", "setup_s"}


def _altered_token(logits):
    """A token altered where it is produced: the runner-up, not the best."""
    return jnp.argsort(logits, axis=-1)[..., -2].astype(jnp.int32)


def _stale_decode(model):
    """A decode step that hands its cache back unchanged."""
    def decode_step(params, cache, tokens):
        _, logits = model.decode_step(params, cache, tokens)
        return cache, logits
    return decode_step


@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    if fault == "altered_token":
        monkeypatch.setattr(adapter, "greedy_sample", _altered_token)
    else:
        monkeypatch.setattr(adapter, "make_decode_fn", _stale_decode)
    out = run_small(small_cell(), 2**31 + 3)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_the_fp8_control_is_not_correct(seed):
    """At this size sound runs read 0 to 0.0049 and the control 0.029 to
    0.093 (seeds 11-16), so the small cell's limit is 0.015."""
    cell = small_cell()
    server = cellrun.build(cell, seed)
    served = cellrun.serve(server, cell, seed, 2.0)
    picked = check.sample(served.records, seed, 96)
    gaps = check.served_gaps(cell, seed, picked, control=True)
    limit = cell.params["check"]["max_logit_gap"]
    assert gaps["served"] <= limit < gaps["control"]
    assert check.correct(check.checks(gaps["served"], served, limit))
    assert not check.correct(check.checks(gaps["control"], served, limit))
