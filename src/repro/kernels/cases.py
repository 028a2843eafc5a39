"""Each Pallas kernel at real widths, with its reference and tolerance.

One case per kernel of this package, at the widths it meets in a model:
granite-3-2b's attention (32 query heads, 8 KV heads, head_dim 64),
rwkv6-7b's WKV (64 heads of 64), an RG-LRU recurrence of width 4096 and a
512×2048×8192 int8 matmul.  ``tests/test_tpu_compile.py`` compiles every
case for a TPU v5e from the shapes of ``make``; ``chip_smoke.py`` runs
every case on the chip against ``ref`` (evaluated at float32 matmul
precision).  Tolerances are those of the interpret-mode tests in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _i8
from repro.kernels import ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import wkv6 as _wkv


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """``kernel(*make(key), interpret=...)`` against ``ref(*make(key))``."""
    kernel: Callable
    ref: Callable
    make: Callable[[jax.Array], Tuple]
    atol: float
    rtol: float


def _normal(key, i, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(key, i), shape)
            * scale).astype(dtype)


def _attention_inputs(key):          # granite-3-2b, one 2048-token prompt
    bf = jnp.bfloat16
    return (_normal(key, 0, (1, 32, 2048, 64), bf),
            _normal(key, 1, (1, 8, 2048, 64), bf),
            _normal(key, 2, (1, 8, 2048, 64), bf))


def _decode_inputs(key):             # granite-3-2b, batch 8, 2048-slot cache
    bf = jnp.bfloat16
    lengths = jax.random.randint(jax.random.fold_in(key, 3), (8,), 1, 2049)
    return (_normal(key, 0, (8, 32, 64), bf),
            _normal(key, 1, (8, 8, 2048, 64), bf),
            _normal(key, 2, (8, 8, 2048, 64), bf),
            lengths.astype(jnp.int32))


def _wkv6_inputs(key):               # rwkv6-7b: 64 heads of 64, 2048 tokens
    shape = (1, 2048, 64, 64)
    return (_normal(key, 0, shape, scale=0.5),
            _normal(key, 1, shape, scale=0.5),
            _normal(key, 2, shape),
            -jnp.exp(_normal(key, 3, shape, scale=0.5)),
            _normal(key, 4, (64, 64), scale=0.1),
            _normal(key, 5, (1, 64, 64, 64), scale=0.1))


def _rglru_inputs(key):              # RG-LRU width 4096, 2048 tokens
    a = jax.random.uniform(jax.random.fold_in(key, 0), (1, 2048, 4096),
                           minval=0.8, maxval=0.999)
    return (a, _normal(key, 1, (1, 2048, 4096), scale=0.1),
            _normal(key, 2, (1, 4096)))


def _int8_inputs(key):               # 512 tokens × (2048 → 8192)
    x_q, sx = ref.quantize_rowwise(_normal(key, 0, (512, 2048)))
    w_t, sw = ref.quantize_rowwise(_normal(key, 1, (8192, 2048)))
    return x_q, w_t.T, sx, sw


CASES: Dict[str, KernelCase] = {
    "flash_attention": KernelCase(
        functools.partial(_fa.flash_attention, causal=True),
        functools.partial(ref.mha_reference, causal=True),
        _attention_inputs, atol=2e-2, rtol=2e-1),
    "decode_attention": KernelCase(
        _dec.decode_attention, ref.decode_attention_reference,
        _decode_inputs, atol=2e-2, rtol=2e-1),
    "wkv6": KernelCase(_wkv.wkv6, ref.wkv6_reference, _wkv6_inputs,
                       atol=2e-4, rtol=1e-3),
    "rglru_scan": KernelCase(_rg.rglru_scan, ref.rglru_reference,
                             _rglru_inputs, atol=1e-5, rtol=1e-5),
    "int8_matmul": KernelCase(_i8.int8_matmul, ref.int8_matmul_reference,
                              _int8_inputs, atol=1e-3, rtol=1e-4),
}
