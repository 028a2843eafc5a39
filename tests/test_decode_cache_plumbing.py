"""The served decode step moves no more of the KV cache than it must.

Each layer of the scan only reads its slice of the stacked cache; the new
keys and values come out of the scan and are written once, one slot per
row, after it.  Checked on the optimized HLO of ``serve_decode`` (greedy
sampling included, the cache donated) compiled for a described, not
attached, TPU v5e at the benchmark's chat shapes: the layout copies and
whole-layer writes that cost the step its time show up there by name and
shape, without a chip.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import greedy_sample, make_decode_fn, serving_config

SLOTS = 960      # the chat cells' cache length; no other dimension is 960
_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z\-]+)\((.*?)\)")
_DIMS = re.compile(r"\[([\d,]*)\]")


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def compiled_decode(arch: str, layers, batch: int, chip) -> str:
    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    model = build_model(serving_config(cfg))
    decode = make_decode_fn(model)

    def serve_decode(params, cache, tokens):
        cache, logits = decode(params, cache, tokens)
        return cache, greedy_sample(logits)

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_cache(batch, SLOTS)))
    tokens = on_chip(jax.ShapeDtypeStruct((batch,), jnp.int32))
    return jax.jit(serve_decode, donate_argnums=(1,)).lower(
        params, cache, tokens).compile().as_text()


def parse(hlo: str):
    """{computation: [(name, dims of the first result, opcode, operands,
    line)]}, and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTR.match(line) if cur else None
        if m:
            d = _DIMS.search(m.group(2))
            dims = tuple(int(x) for x in d.group(1).split(",") if x) if d else ()
            operands = re.findall(r"%([^\s,)]+)", m.group(4))
            comps[cur].append((m.group(1), dims, m.group(3), operands, line))
    return comps, entry


def reachable(comps, root: str):
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for *_, line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([^\s,}]+)",
                               line)
    return seen


CELLS = {
    # arch, layers, batch; the decode loop body's top-level ops before
    # the cache was read-only in the scan: 88 and 84
    "granite-3-2b.chat": ("granite-3-2b", None, 16, 88),
    "granite-8b-18l.chat": ("granite-8b", 18, 8, 84),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_decode_moves_only_new_cache_entries(cell, one_chip):
    arch, layers, batch, body_before = CELLS[cell]
    comps, entry = parse(compiled_decode(arch, layers, batch, one_chip))
    shapes = {c: {name: dims for name, dims, *_ in instrs}
              for c, instrs in comps.items()}
    loops = [line for *_, op, _, line in comps[entry] if op == "while"]
    assert len(loops) == 1, "one scan over the layers"
    body = re.search(r"body=%([^\s,]+)", loops[0]).group(1)
    in_body = reachable(comps, body)

    writes = 0
    for c, instrs in comps.items():
        for name, dims, op, operands, line in instrs:
            if SLOTS not in dims:
                continue
            assert op not in ("copy", "copy-start"), (
                f"a copy of a cache-shaped array: {line.strip()[:200]}")
            if op != "dynamic-update-slice":
                continue
            assert c not in in_body, (
                f"the decode loop writes into the cache: {line.strip()[:200]}")
            update = shapes[c][operands[1]]
            assert update[dims.index(SLOTS)] == 1, (
                f"a write of more than one slot: {line.strip()[:200]}")
            writes += 1
    assert writes >= 3 * batch, "each row's key, value and position written"
    # no per-row work has moved into the loop
    assert len(comps[body]) <= body_before + 8
