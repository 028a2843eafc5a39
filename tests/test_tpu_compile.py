"""Every Pallas kernel compiles for one TPU v5e chip at real widths.

Interpret mode on the CPU accepts block shapes and in-kernel operations
that the chip's kernel compiler refuses, so each kernel of
``repro.kernels.cases`` is also compiled here, with ``interpret=False``,
for a described (not attached) v5e chip; so are the served prefill, which
takes the flash kernel there, the decode step, which does not, and a
forward over a 2×2 mesh, which does not either.  The topology is described
in a fixture: only the worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.cases import CASES


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache the environment set
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in jax.eval_shape(case.make, jax.random.key(0))]
    fn = jax.jit(lambda *a: case.kernel(*a, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------ served steps
def _on(sharding, tree):
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


@pytest.fixture
def on_chip(monkeypatch):
    """The model code as it runs on a chip: a compile for a described chip
    still sees the CPU backend, which would keep ``attend``."""
    from repro.kernels import ops
    from repro.models import layers as L
    monkeypatch.setattr(L, "interpret_mode", lambda: False)
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _served(arch, step, batch, chip, seq):
    """The optimized HLO of a served step (greedy sampling included) of the
    reduced ``arch`` for the described chip: a prefill of ``seq`` tokens,
    or a decode step over a cache of ``seq`` slots."""
    from repro.configs import ARCHS
    from repro.models import build_model, reduced
    from repro.serving.engine import (greedy_sample, make_decode_fn,
                                      make_prefill_fn, serving_config)
    model = build_model(serving_config(reduced(ARCHS[arch])))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = _on(jax.tree.map(lambda _: chip, shapes), shapes)
    row = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip)
    if step == "prefill":
        prefill = make_prefill_fn(model)

        def serve_prefill(params, tokens, lengths):
            cache, logits = prefill(params, tokens, lengths)
            return cache, greedy_sample(logits)
        fn = jax.jit(serve_prefill)
        args = (jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=chip),
                row)
    else:
        decode = make_decode_fn(model)

        def serve_decode(params, cache, tokens):
            cache, logits = decode(params, cache, tokens)
            return cache, greedy_sample(logits)
        cache = jax.eval_shape(lambda: model.init_cache(batch, seq))
        fn = jax.jit(serve_decode, donate_argnums=(1,))
        args = (_on(jax.tree.map(lambda _: chip, cache), cache), row)
    return fn.lower(params, *args).compile().as_text()


def _kernels(hlo: str):
    return [line for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_prefill_takes_the_flash_kernel(one_chip, on_chip):
    """On a chip the dense prefill's attention is the flash kernel, under
    ``attn_core``, and no float32 score square is left."""
    S = 256
    hlo = _served("granite-3-2b", "prefill", 2, one_chip, S)
    kernels = _kernels(hlo)
    assert kernels and all("/attn_core/" in line for line in kernels)
    squares = [d for d in re.findall(r"f32\[([\d,]+)\]", hlo)
               if len(d.split(",")) == 5 and d.endswith(f",{S},{S}")]
    assert not squares, f"a float32 score square: f32[{squares[0]}]"


# cell: arch (reduced) and its largest batch
DECODE = {
    "granite-3-2b.chat": ("granite-3-2b", 16),
    "granite-8b-18l.chat": ("granite-8b", 8),
    "granite-3-2b.long": ("granite-3-2b", 4),
    "granite-8b-18l.long": ("granite-8b", 2),
    "granite-4.0-h-micro.chat": ("granite-4.0-h-micro", 16),
}


@pytest.mark.parametrize("cell", sorted(DECODE))
def test_decode_is_unchanged(cell, one_chip, on_chip):
    """Each benchmark cell's decode step, at its largest batch, keeps
    ``attend_cached``: no kernel under ``attn_core``."""
    arch, batch = DECODE[cell]
    hlo = _served(arch, "decode", batch, one_chip, 272)
    assert "/attn_core/" in hlo
    assert not [k for k in _kernels(hlo) if "/attn_core/" in k]


def test_sharded_forward_keeps_attend(v5e_2x2, on_chip):
    """A forward over a 2×2 mesh, parameters split as training splits
    them, keeps ``attend``: the kernel is one device's code, and the
    partitioner would gather q, k and v around it.  On one chip the same
    forward takes the kernel."""
    from repro.configs import ARCHS
    from repro.dist import sharding as shd
    from repro.models import build_model, reduced
    model = build_model(reduced(ARCHS["granite-3-2b"]))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    mesh = Mesh(np.asarray(v5e_2x2.devices).reshape(2, 2), ("data", "model"))
    split = shd.tree_shardings(shapes, model.logical_axes(), shd.TRAIN_RULES,
                               mesh)
    chip = SingleDeviceSharding(v5e_2x2.devices[0])
    forward = jax.jit(lambda p, t: model.forward(p, t)[0])
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    hlo = {}
    for name, params, rows in (
            ("mesh", _on(split, shapes),
             NamedSharding(mesh, PartitionSpec("data"))),
            ("chip", _on(jax.tree.map(lambda _: chip, shapes), shapes),
             chip)):
        t = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=rows)
        hlo[name] = forward.lower(params, t).compile().as_text()
    assert _kernels(hlo["chip"])
    assert not _kernels(hlo["mesh"])
    assert "/attn_core/" in hlo["mesh"]
