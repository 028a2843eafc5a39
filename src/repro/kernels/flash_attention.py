"""Flash attention (prefill) Pallas TPU kernel.

Blockwise online-softmax self-attention over right-padded rows, with GQA,
sliding windows and logit soft-capping.  Grid = (B, K, S/bq, S/bk); the
K/V axis is the fastest (sequentially iterated on TPU), with the running
max, sum and output accumulator of each query block held in VMEM scratch.

* **GQA folding.**  A grid step takes all G = H/K query heads that share a
  KV head, a (G·bq, d) query tile against one (bk, d) K/V tile, so K and V
  are read once per group and the matmuls are G times taller.
* **Operands.**  The MXU takes q, k and v in their own dtype, and the
  probabilities in v's dtype, accumulating in float32; the softmax
  statistics stay float32.
* **Block skipping** (``_span``): a K/V block wholly above the causal
  diagonal, out of every query's window, or at or past the row's length
  (``lengths``, scalar-prefetched) does no work, and its K/V index is
  clamped to a block that runs, so no DMA is issued for it.  A query block
  wholly past the row's length writes zeros.  Only blocks that cross the
  diagonal, the window's edge or the length build the element mask.

A live query never sees a padded key under a causal mask with right
padding; a padded query in a live block sees the row's live keys, so every
output is finite.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIN_BLOCK = 128          # the chip's lane width: blocks are multiples of it
NEG_INF = -1e30

# (block_q, block_k) aimed for; ``block_sizes`` takes the largest multiples
# of MIN_BLOCK up to these that divide the sequence.  Checked only by
# compiling for a described TPU v5e at the benchmark's prefill shapes (32
# query heads over 8 KV heads, head sizes 64 and 128, 768 and 2048 tokens),
# where it fits the default scoped VMEM; not chosen by a run on the chip.
BLOCKS = (256, 512)


def block_sizes(seq_len: int) -> Tuple[int, int]:
    """(block_q, block_k) for a sequence length.  A sequence that is not a
    multiple of MIN_BLOCK is one block (interpret mode)."""
    def fit(target):
        if seq_len % MIN_BLOCK:
            return seq_len
        b = min(target, seq_len)
        while seq_len % b:
            b -= MIN_BLOCK
        return b
    return fit(BLOCKS[0]), fit(BLOCKS[1])


def _span(i, length, *, causal: bool, window: int, block_q: int,
          block_k: int):
    """(first, last, live): the K/V blocks query block ``i`` of a row of
    ``length`` attends to, and whether any of its queries is live."""
    q_start = i * block_q
    last = jnp.maximum(length - 1, 0) // block_k
    if causal:
        last = jnp.minimum(last, (q_start + block_q - 1) // block_k)
    first = 0
    if window > 0:      # the oldest query's oldest key in its window
        first = jnp.maximum(q_start - window + 1, 0) // block_k
    return first, last, q_start < length


def _flash_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, causal: bool, window: int,
                  softcap: float, sm_scale: float, block_q: int,
                  block_k: int):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length = lengths_ref[b]
    groups, _, d = q_ref.shape
    q_start, k_start = i * block_q, j * block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last, live = _span(i, length, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)
    run = live & (j >= first) & (j <= last)
    # does any (query, key) pair of the block fall outside the mask?
    edge = k_start + block_k > length
    if causal:
        edge |= k_start + block_k - 1 > q_start
    if window > 0:
        edge |= k_start <= q_start + block_q - 1 - window

    def step(masked: bool):
        q = q_ref[...].reshape(groups * block_q, d)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < length
            if causal:
                mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask[None], s.reshape(groups, block_q, block_k),
                          NEG_INF).reshape(groups * block_q, block_k)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when(run & edge)(functools.partial(step, True))
    pl.when(run & ~edge)(functools.partial(step, False))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[...]
        out = acc_scr[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = out.reshape(groups, block_q, d).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    lengths: Optional[jnp.ndarray] = None, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0,
                    interpret: bool = False) -> jnp.ndarray:
    """Self-attention of right-padded rows.

    q: (B, H, S, d); k/v: (B, K, S, d); lengths: (B,) live tokens per row
    (None: all S).  Returns (B, H, S, d): zeros where a whole query block
    is padding.  Blocks of ``block_sizes(S)``.
    """
    B, H, S, d = q.shape
    K = k.shape[1]
    assert H % K == 0 and k.shape[2] == S
    G = H // K
    block_q, block_k = block_sizes(S)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    span = functools.partial(_span, causal=causal, window=window,
                             block_q=block_q, block_k=block_k)

    def q_block(b, h, i, j, lens):          # a dead query block keeps the
        last_q = jnp.maximum(lens[b] - 1, 0) // block_q   # last live one's
        return b, h, 0, jnp.where(span(i, lens[b])[2], i, last_q), 0

    def kv_block(b, h, i, j, lens):         # a skipped block keeps the index
        first, last, live = span(i, lens[b])  # of the one before it
        row_last = jnp.maximum(lens[b] - 1, 0) // block_k
        return b, h, jnp.where(live, jnp.clip(j, first, last), row_last), 0

    sq = pl.Squeezed()
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, softcap=softcap,
        sm_scale=1.0 / math.sqrt(d), block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, S // block_q, S // block_k),
            in_specs=[
                pl.BlockSpec((sq, sq, G, block_q, d), q_block),
                pl.BlockSpec((sq, sq, block_k, d), kv_block),
                pl.BlockSpec((sq, sq, block_k, d), kv_block),
            ],
            out_specs=pl.BlockSpec((sq, sq, G, block_q, d),
                                   lambda b, h, i, j, lens: (b, h, 0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((G * block_q, 1), jnp.float32),
                pltpu.VMEM((G * block_q, 1), jnp.float32),
                pltpu.VMEM((G * block_q, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(lengths.astype(jnp.int32), q.reshape(B, K, G, S, d), k, v)
    return out.reshape(B, H, S, d)
