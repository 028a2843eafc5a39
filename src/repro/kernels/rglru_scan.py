"""RG-LRU gated-linear-recurrence Pallas TPU kernel.

The recurrence s_t = a_t ⊙ s_{t-1} + b_t is elementwise over the RNN width
R, so the natural TPU mapping is: R on the lane dimension (blocked br),
sequence chunks streamed through VMEM, state carried in VMEM scratch, and
the per-chunk recurrence unrolled as a vector loop (each step is one VPU
FMA over (br,) lanes — there is no matmul to win back, so a sequential
in-VMEM loop IS the roofline-optimal form; HBM traffic = read a,b once,
write s once).  Grid = (B, R/br, S/T), chunk axis sequential.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128
DEFAULT_BLOCK_R = 512
SUBLANES = 8   # rows per aligned (8, br) tile read from / written to VMEM


def _rglru_kernel(a_ref, b_ref, s0_ref, out_ref, last_ref, state_scr, *,
                  chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)      # (1, br)

    def tile(i, s):
        rows = pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)
        a = a_ref[0, rows, :].astype(jnp.float32)           # (8, br)
        b = b_ref[0, rows, :].astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        out = jnp.zeros_like(a)
        for t in range(SUBLANES):
            s = a[t:t + 1] * s + b[t:t + 1]
            out = jnp.where(row == t, s, out)
        out_ref[0, rows, :] = out.astype(out_ref.dtype)
        return s

    s = jax.lax.fori_loop(0, chunk // SUBLANES, tile, state_scr[...])
    state_scr[...] = s

    @pl.when(c == nc - 1)
    def _final():
        last_ref[0] = s.astype(last_ref.dtype)


def rglru_scan(a: jnp.ndarray, b: jnp.ndarray, s0: jnp.ndarray, *,
               chunk: int = DEFAULT_CHUNK, block_r: int = DEFAULT_BLOCK_R,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a/b: (B, S, R) fp32; s0: (B, R) fp32 → (s_seq (B,S,R), s_last (B,R))."""
    B, S, R = a.shape
    chunk = min(chunk, S)
    block_r = min(block_r, R)
    assert chunk % SUBLANES == 0 and S % chunk == 0 and R % block_r == 0
    grid = (B, R // block_r, S // chunk)

    seq_spec = pl.BlockSpec((1, chunk, block_r), lambda bi, ri, c: (bi, c, ri))
    # state rows are (B, 1, R) so a block's last two dims are (1, block_r)
    vec_spec = pl.BlockSpec((1, 1, block_r), lambda bi, ri, c: (bi, 0, ri))
    out, last = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[seq_spec, seq_spec, vec_spec],
        out_specs=[seq_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct((B, 1, R), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret,
    )(a, b, s0.reshape(B, 1, R))
    return out, last.reshape(B, R)
