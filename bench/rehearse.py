"""Compiles each cell's steps at its real sizes for a described TPU v5e.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

No chip is needed: the TPU compiler builds the prefill and decode steps of
each cell's largest batch, and the reference's layer at its block size,
for a v5e that is described and not attached, and prints what
``memory_analysis()`` says each program holds.  A cell whose steps do not
fit is refused here, before any chip time is spent on it.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
GB = 1e9


def _footprint(compiled) -> dict:
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {"arguments_gb": m.argument_size_in_bytes / GB,
            "outputs_gb": m.output_size_in_bytes / GB,
            "aliased_gb": m.alias_size_in_bytes / GB,
            "temporaries_gb": m.temp_size_in_bytes / GB,
            "held_gb": held / GB}


def rehearse(name: str, chip) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import adapter, check, spec

    cell = spec.load(name)
    ref = check.reference(cell.config["family"])
    dims, b = cell.dims, max(cell.params["batcher"]["preferred"])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    words = sds((2,), jnp.uint32)
    make = adapter.params_fn(cell.config, dims)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(make, words))
    server = adapter.Server(adapter.model_config(cell.config), params,
                            cell.pad, cell.max_len)
    out = {"cell": name, "batch": b,
           "weights": _footprint(make.lower(words).compile())}
    toks, lens = sds((b, cell.pad), jnp.int32), sds((b,), jnp.int32)
    out["prefill"] = _footprint(
        server._prefill.lower(server.params, toks, lens).compile())
    cache, tok = jax.eval_shape(server._prefill, server.params, toks, lens)
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), cache)
    out["decode"] = _footprint(server._decode.lower(
        server.params, cache, sds(tok.shape, tok.dtype)).compile())
    rows = max(1, check._SCORE_BYTES
               // (dims["heads"] * cell.max_len ** 2 * 4))
    key = tuple(sorted(dims.items()))
    w = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: ref._layer_weights(jnp.zeros(2, jnp.uint32), 0, key,
                                   cell.config["dtype"])))
    x = sds((rows, cell.max_len, dims["d"]), jnp.float32)
    out["reference_layer"] = _footprint(
        ref._layer.lower(x, w, "f32").compile())
    out["reference_rows"] = rows
    return out


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    with open(ROOT / "BENCHMARK.json") as f:
        names = argv or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        print(json.dumps(rehearse(name, chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
