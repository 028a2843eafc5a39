"""The served steps name the model's parts on the device.

Each jitted step carries its ``jax.named_scope`` names in the op metadata
of its lowering, where the profiler's trace picks them up.  Checked on the
``reduced()`` size of each family, lowered only (no compile, no run).
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model, reduced
from repro.serving.engine import (greedy_sample, make_decode_fn,
                                  make_prefill_fn, serving_config)

DENSE = {"embed", "attn_qkv", "kv_write", "attn_core", "attn_out", "mlp",
         "logits", "sample"}
_LOC = re.compile(r'loc\("([^"]*)"')


def served_scopes(arch: str, batch: int = 2, pad: int = 32, max_len: int = 48):
    """Scope names in the lowered serve_prefill and serve_decode, composed
    as a server composes them: each step, then greedy sampling."""
    model = build_model(serving_config(reduced(get_config(arch))))
    prefill = make_prefill_fn(model, max_len=max_len)
    decode = make_decode_fn(model)

    def serve_prefill(params, tokens, lengths):
        cache, logits = prefill(params, tokens, lengths)
        return cache, greedy_sample(logits)

    def serve_decode(params, cache, tokens):
        cache, logits = decode(params, cache, tokens)
        return cache, greedy_sample(logits)

    params = jax.eval_shape(model.init, jax.random.key(0))
    toks = jax.ShapeDtypeStruct((batch, pad), jnp.int32)
    lens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cache, tok = jax.eval_shape(serve_prefill, params, toks, lens)
    out = {}
    for name, lowered in (
            ("prefill", jax.jit(serve_prefill).lower(params, toks, lens)),
            ("decode", jax.jit(serve_decode).lower(params, cache, tok))):
        assert f"jit_serve_{name}" == lowered.as_text().split(
            "module @", 1)[1].split()[0]
        paths = _LOC.findall(lowered.as_text(debug_info=True))
        out[name] = {part for p in paths for part in p.split("/")}
    return out


@pytest.fixture(scope="module")
def granite():
    return served_scopes("granite-3-2b")


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_dense_steps_carry_every_scope(granite, step):
    assert DENSE <= granite[step]


def test_no_recurrent_or_expert_scope_on_the_dense_path(granite):
    for step in ("prefill", "decode"):
        assert not {"moe", "rglru", "rwkv_time_mix",
                    "rwkv_channel_mix"} & granite[step]


@pytest.mark.parametrize("arch,names", [
    ("rwkv6-7b", {"rwkv_time_mix", "rwkv_channel_mix", "embed", "logits",
                  "sample"}),
    ("recurrentgemma-9b", {"rglru", "mlp", "attn_core", "kv_write",
                           "logits", "sample"}),
    ("granite-moe-3b-a800m", {"moe", "attn_core", "kv_write", "logits",
                              "sample"}),
])
def test_other_families_name_their_blocks(arch, names):
    got = served_scopes(arch)
    for step in ("prefill", "decode"):
        assert names <= got[step], (step, names - got[step])
