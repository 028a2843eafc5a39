"""Public jit'd wrappers over the Pallas kernels.

On a TPU the kernels compile to Mosaic.  On the CPU backend (the test
suite) they run in Pallas's ``interpret=True`` mode, so every call is
still checked end to end; ``interpret_mode()`` says which one a call
gets, and is decided from the backend when a wrapper is traced.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _i8
from repro.kernels import rglru_scan as _rg
from repro.kernels import wkv6 as _wkv


def interpret_mode() -> bool:
    """True where the kernels run interpreted: on the CPU backend."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K) -> jnp.ndarray:
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("softcap", "block_k"))
def decode_attention(q, k, v, lengths, *, softcap: float = 0.0,
                     block_k: int = _dec.DEFAULT_BLOCK_K) -> jnp.ndarray:
    return _dec.decode_attention(q, k, v, lengths, softcap=softcap,
                                 block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, logw, u, state0,
         *, chunk: int = _wkv.DEFAULT_CHUNK) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return _wkv.wkv6(r, k, v, logw, u, state0, chunk=chunk,
                     interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk", "block_r"))
def rglru_scan(a, b, s0, *, chunk: int = _rg.DEFAULT_CHUNK,
               block_r: int = _rg.DEFAULT_BLOCK_R):
    return _rg.rglru_scan(a, b, s0, chunk=chunk, block_r=block_r,
                          interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def int8_matmul(x_q, w_q, sx, sw, *, bm: int = _i8.DEFAULT_BM,
                bn: int = _i8.DEFAULT_BN, bk: int = _i8.DEFAULT_BK):
    return _i8.int8_matmul(x_q, w_q, sx, sw, bm=bm, bn=bn, bk=bk,
                           interpret=interpret_mode())
