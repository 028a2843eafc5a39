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
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import wkv6 as _wkv


def interpret_mode() -> bool:
    """True where the kernels run interpreted: on the CPU backend."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0) -> jnp.ndarray:
    """Self-attention of right-padded rows, block sizes from the shape.
    Differentiable: the backward pass is the plain reference's."""
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[2], jnp.int32)
    return _flash(q, k, v, lengths, causal, window, softcap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, lengths, causal, window, softcap):
    return _fa.flash_attention(q, k, v, lengths, causal=causal, window=window,
                               softcap=softcap, interpret=interpret_mode())


def _flash_fwd(q, k, v, lengths, causal, window, softcap):
    return (_flash(q, k, v, lengths, causal, window, softcap),
            (q, k, v, lengths))


def _flash_bwd(causal, window, softcap, res, g):
    q, k, v, lengths = res
    _, vjp = jax.vjp(functools.partial(
        _ref.mha_reference, causal=causal, window=window, softcap=softcap,
        lengths=lengths), q, k, v)
    return (*vjp(g), None)


_flash.defvjp(_flash_fwd, _flash_bwd)


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
