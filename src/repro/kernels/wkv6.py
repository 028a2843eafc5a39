"""RWKV6 chunked-WKV Pallas TPU kernel.

Grid = (B, H, S/T): the chunk axis is iterated sequentially (TPU grid
order), carrying the (N, N) per-head state in VMEM scratch.  Within a
chunk the pairwise decay tensor exp(Σ logw) is materialised in VMEM —
it is ≤ 1 everywhere so this is overflow-safe — giving exact WKV with
two (T,N)×(N,N)-shaped MXU contractions per chunk instead of a length-S
sequential recurrence.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                o_ref, sf_ref, state_scr, *, chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    rr = r_ref[0, 0].astype(jnp.float32)         # (T, N)
    kk = k_ref[0, 0].astype(jnp.float32)
    vv = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, N)
    state = state_scr[...]                       # (N, N)
    N = state.shape[0]

    def mm(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    # inclusive prefix sum over the chunk, as a lower-triangular matmul
    # (the TPU's kernel compiler has no cumsum)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lc = mm((s_idx <= t_idx).astype(jnp.float32), lw)
    lc_excl = lc - lw
    lc_last = lc[chunk - 1:chunk, :]             # (1, N)
    o_inter = mm(rr * jnp.exp(lc_excl), state)
    # A[t, s] = Σ_d r_td k_sd e^{lc_excl_td − lc_sd}, s < t, one column per
    # key.  For s < t the exponent is ≤ 0 (a decay); clamping it keeps the
    # masked s ≥ t entries finite.
    A = jnp.zeros((chunk, chunk), jnp.float32)
    for s in range(chunk):
        decay = jnp.exp(jnp.minimum(lc_excl - lc[s:s + 1, :], 0.0))
        col = jnp.sum(rr * kk[s:s + 1, :] * decay, axis=1, keepdims=True)
        A = A + jnp.where(s_idx == s, col, 0.0)
    A = jnp.where(s_idx < t_idx, A, 0.0)
    diag = jnp.sum(rr * u * kk, axis=1, keepdims=True)          # (T, 1)
    o_ref[0, 0] = (o_inter + mm(A, vv) + diag * vv).astype(o_ref.dtype)

    # state ← diag(e^{lc_last}) · state + Σ_t (k_t e^{lc_last − lc_t})ᵀ v_t
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
    k_dec = kk * jnp.exp(lc_last - lc)
    state_scr[...] = (mm(jnp.where(eye, jnp.exp(lc_last), 0.0), state)
                      + mm(k_dec, vv, ((0,), (0,))))

    @pl.when(c == nc - 1)
    def _final():
        sf_ref[0, 0] = state_scr[...].astype(sf_ref.dtype)


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, logw: jnp.ndarray,
         u: jnp.ndarray, state0: jnp.ndarray, *, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r/k/v/logw: (B, S, H, N); u: (H, N); state0: (B, H, N, N) fp32.

    The kernel reads heads-major (B, H, S, N) so that every block's last
    two dims are (chunk, N), the tiling the TPU's compiler requires.
    """
    B, S, H, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    grid = (B, H, S // chunk)
    heads_major = lambda x: x.transpose(0, 2, 1, 3)

    io_spec = pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0))
    out, state = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            io_spec, io_spec, io_spec, io_spec,
            pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            io_spec,
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, N), r.dtype),
            jax.ShapeDtypeStruct(state0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(*map(heads_major, (r, k, v, logw)), u.reshape(H, 1, N), state0)
    return heads_major(out), state
