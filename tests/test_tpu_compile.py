"""Every Pallas kernel compiles for one TPU v5e chip at real widths.

Interpret mode on the CPU accepts block shapes and in-kernel operations
that the chip's kernel compiler refuses, so each kernel of
``repro.kernels.cases`` is also compiled here, with ``interpret=False``,
for a described (not attached) v5e chip.  The topology is described in a
fixture: only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import CASES


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


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in jax.eval_shape(case.make, jax.random.key(0))]
    fn = jax.jit(lambda *a: case.kernel(*a, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
