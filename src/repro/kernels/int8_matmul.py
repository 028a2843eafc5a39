"""Int8×int8 → fp32 quantized matmul Pallas TPU kernel.

The paper's pre-deployment pipeline includes an INT8-conversion step
(§2.1); this kernel is the serving-side half: weights stored int8 with
per-output-channel scales, activations quantized per-row on the fly, MXU
int8 matmul accumulating int32 in VMEM, dequantised once at the end.
Tiling: grid = (M/bm, N/bn, K/bk), K fastest with an int32 accumulator.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _int8_mm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_scr):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]
    w = w_ref[...]
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _final():
        sx = sx_ref[...].astype(jnp.float32)      # (bm, 1)
        sw = sw_ref[...].astype(jnp.float32)      # (1, bn)
        o_ref[...] = (acc_scr[...].astype(jnp.float32)
                      * sx * sw).astype(o_ref.dtype)


def int8_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, sx: jnp.ndarray,
                sw: jnp.ndarray, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, out_dtype=jnp.float32,
                interpret: bool = False) -> jnp.ndarray:
    """x_q: (M, K) int8; w_q: (K, N) int8; sx: (M,); sw: (N,) → (M, N)."""
    M, K = x_q.shape
    N = w_q.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)

    return pl.pallas_call(
        _int8_mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            # scales as a column and a row: 2-D blocks match XLA's tiling
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, sx.reshape(M, 1), sw.reshape(1, N))
