"""The dense family gives what the harness computed before families were
files of their own: the same parameter counts, the same operations and
bytes of every served step, and the same seeded weights, bit for bit."""
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec, weights  # noqa: E402
from bench.families import dense  # noqa: E402

BF16 = 2
CONFIGS = {"granite-3-2b": 2_533_531_648, "granite-8b-18l": 4_127_346_688}
LENGTHS = [[1], [32, 768], [1024, 2048, 1536, 1111], [5, 3]]
CONTEXTS = [[2], [33, 769, 960], [2072, 1025], [6, 4]]


# The counts as the harness computed them before they moved to the family.
def _layer_params(m):
    d, h, k, hd, ff = m["d"], m["heads"], m["kv_heads"], m["head_dim"], m["ff"]
    return 2 * d * h * hd + 2 * d * k * hd + 3 * d * ff + 2 * d


def _nonembed(m):
    return m["layers"] * _layer_params(m) + m["d"]


def _all(m):
    return _nonembed(m) + m["vocab"] * m["d"]


def _kv(m):
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * BF16


def _attn(m, pairs):
    return 4.0 * m["layers"] * m["heads"] * m["head_dim"] * pairs


def _head(m):
    return 2.0 * m["d"] * m["vocab"]


PARENT = {
    "prefill_flops": lambda m, ls: sum(2.0 * _nonembed(m) * n
                                       + _attn(m, n * (n + 1) / 2) + _head(m)
                                       for n in ls),
    "prefill_bytes": lambda m, ls: _all(m) * BF16 + sum(ls) * _kv(m),
    "decode_flops": lambda m, cs: sum(2.0 * _nonembed(m) + _attn(m, c)
                                      + _head(m) for c in cs),
    "decode_bytes": lambda m, cs: _all(m) * BF16 + sum(cs) * _kv(m),
    "decode_attn_counts": lambda m, cs: (_attn(m, sum(cs)), sum(cs) * _kv(m)),
    "prefill_attn_flops": lambda m, ls: _attn(
        m, sum(n * (n + 1) / 2 for n in ls)),
}


def config(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dims_are_the_configuration_files_sizes(name):
    c = config(name)
    assert spec.family(c["family"]) is dense
    assert dense.dims(c) == {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "ff": c["intermediate_size"], "vocab": c["vocab_size"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_all_params_as_published_in_the_configuration(name):
    m = dense.dims(config(name))
    assert dense.all_params(m) == _all(m) == CONFIGS[name]


@pytest.mark.parametrize("count", sorted(PARENT))
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_equal_the_parents_exactly(name, count):
    m = dense.dims(config(name))
    sizes = LENGTHS if count.startswith("prefill") else CONTEXTS
    for s in sizes:
        assert getattr(dense, count)(m, s) == PARENT[count](m, s)


DIMS = {"layers": 2, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
        "ff": 16, "vocab": 32}
# sha256 over (leaf path, bytes) in path order of all_layers at DIMS from
# seed 2**40 + 12345, as the harness drew them before families were files
HASHES = {
    "bfloat16": "be75b90136d7a766b71faad5a2d9c165"
                "f4b6d39ce82c8b94309e29a025f404da",
    "float32": "005b4906b6d36eb1839b61961d07c797"
               "87693826c21864892b8144106eccb596",
}


@pytest.mark.parametrize("dtype", sorted(HASHES))
def test_seeded_weights_are_unchanged(dtype):
    make = jax.jit(lambda s: weights.all_layers(s, DIMS, jnp.dtype(dtype),
                                                dense))
    w = make(weights.seed_words(2**40 + 12345))
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves, key=lambda x: jax.tree_util.keystr(x[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == HASHES[dtype]
