"""Core model building blocks, written functionally (init fn + apply fn).

Every block here is pure JAX, except that self-attention takes the flash
kernel of ``repro.kernels`` where the kernels are compiled for a chip and
the shapes allow (``_flash_ok``).  Parameter pytrees are plain nested
dicts; each init also has a ``*_axes`` twin returning the logical sharding
axes of each leaf (consumed by ``repro.dist.sharding``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain_act, constrain_attn_q
from repro.kernels import flash_attention as fa
from repro.kernels import ops as kops
# bound here, so a CPU test can take the kernel path (``_flash_ok``) while
# the kernel itself still runs interpreted
from repro.kernels.ops import interpret_mode

Params = Dict[str, Any]


# Leaves that must stay fp32 even under bf16 compute (log-space decays etc.)
_F32_LEAVES = frozenset({"lam", "decay_w0", "bonus_u", "A_log", "dt_bias", "D"})


def cast_layer_params(params: Params, dtype) -> Params:
    """Mixed precision: cast weights to the compute dtype at point of use
    (fp32 masters stay in the optimizer)."""
    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in _F32_LEAVES or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        return leaf.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------
def _dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_axes() -> Params:
    return {"scale": ("embed",)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: Optional[float]) -> jnp.ndarray:
    """x: (..., S, H, hd) ; positions: broadcastable to (..., S).  Head-major
    x, (B, H, S, hd), takes positions (B, 1, S): as many axes as x has
    before hd.  A ``theta`` of None is NoPE: no rotation."""
    if theta is None:
        return x
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs       # (..., S, half)
    # (..., S, 1, half) over the heads after S; head-major x's positions
    # already have the heads' axis
    heads = (lambda a: a[..., None, :]) if angles.ndim < x.ndim else (
        lambda a: a)
    cos = heads(jnp.cos(angles))
    sin = heads(jnp.sin(angles))
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA + optional local window + optional logit softcap)
# --------------------------------------------------------------------------
def attention_init(key, d: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": _dense_init(kq, d, num_heads * head_dim, dtype).reshape(
            d, num_heads, head_dim),
        "wk": _dense_init(kk, d, num_kv_heads * head_dim, dtype).reshape(
            d, num_kv_heads, head_dim),
        "wv": _dense_init(kv, d, num_kv_heads * head_dim, dtype).reshape(
            d, num_kv_heads, head_dim),
        "wo": _dense_init(ko, num_heads * head_dim, d, dtype).reshape(
            num_heads, head_dim, d),
    }


def attention_axes() -> Params:
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def _softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap and cap > 0.0:
        return cap * jnp.tanh(logits / cap)
    return logits


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           q_pos: jnp.ndarray, k_pos: jnp.ndarray,
           *, causal: bool, window: int = 0, softcap: float = 0.0,
           k_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Grouped-query attention core.

    q: (B, S, H, hd); k/v: (B, T, K, hd); q_pos: (B, S); k_pos: (B, T).
    k_valid: optional (B, T) bool mask of live cache slots.
    Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = _softcap(logits, softcap)
    mask = _attn_mask(q_pos, k_pos, causal, window, k_valid)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _attn_mask(q_pos, k_pos, causal: bool, window, k_valid) -> jnp.ndarray:
    """(B, S, T) bool: which keys each query may see."""
    mask = jnp.ones((q_pos.shape[0], q_pos.shape[1], k_pos.shape[1]), bool)
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    # window may be a traced per-layer scalar (scan over mixed local/global
    # stacks) — apply the mask unconditionally unless statically disabled.
    if window is not None and not (isinstance(window, int) and window <= 0):
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    if k_valid is not None:
        mask &= k_valid[:, None, :]
    return mask


def attend_cached(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  k_new: jnp.ndarray, v_new: jnp.ndarray,
                  q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                  *, window: int = 0, softcap: float = 0.0,
                  k_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One query per row against a head-major cache plus the row's own new
    key and value, which the cache does not hold yet.

    q: (B, 1, H, hd); k/v: (B, K, T, hd); k_new/v_new: (B, K, hd) at
    position q_pos: (B,); k_pos, k_valid: (B, T).  The caller masks out
    of ``k_valid`` the slot the new entry will take.  One softmax over
    the cache and the new entry, as ``attend`` computes it over the cache
    with the new entry written in: causal, windowed and softcapped, in
    float32.  Returns (B, 1, H, hd).
    """
    B, _, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(B, K, H // K, hd).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, k.astype(jnp.float32)) * scale
    logits = _softcap(logits, softcap)
    mask = _attn_mask(q_pos[:, None], k_pos, True, window, k_valid)
    logits = jnp.where(mask[:, None], logits, -1e30)
    # the new entry sits at the query's own position: always visible
    fresh = _softcap(jnp.einsum("bkgd,bkd->bkg", qg,
                                k_new.astype(jnp.float32)) * scale, softcap)
    top = jnp.maximum(logits.max(axis=-1), fresh)
    probs = jnp.exp(logits - top[..., None])
    p_fresh = jnp.exp(fresh - top)
    out = (jnp.einsum("bkgt,bktd->bkgd", probs, v.astype(jnp.float32))
           + p_fresh[..., None] * v_new.astype(jnp.float32)[:, :, None])
    out = out / (probs.sum(axis=-1) + p_fresh)[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _flash_ok(x: jnp.ndarray, window, kv) -> bool:
    """Whether self-attention over x (B, S, D) takes the flash kernel: the
    kernels are compiled for a chip (not interpreted on the CPU), x is not
    spread over several devices (the kernel is one device's code, with no
    rule to partition it), keys and values come from x itself, the window
    is a Python int (not a per-layer scalar a scan slices), and the
    kernel's blocks tile the sequence."""
    return (not interpret_mode() and jax.typeof(x).sharding.mesh.size <= 1
            and kv is None and isinstance(window, int)
            and x.shape[1] % fa.MIN_BLOCK == 0)


def attention_apply(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                    *, rope_theta: Optional[float], causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    kv_pos: Optional[jnp.ndarray] = None,
                    lengths: Optional[jnp.ndarray] = None,
                    return_kv: bool = False):
    """Full attention block: projections + RoPE + attend + output proj.

    When ``kv`` is given it is used as the key/value source (decode against a
    cache, or cross-attention); otherwise self-attention over ``x``, whose
    positions are 0..S-1.  ``lengths`` (B,): each row's keys at or past it
    are padding (None: none are).  Keys and values are returned (B, S, K,
    hd).  Self-attention takes the flash kernel where ``_flash_ok`` says so.
    """
    if _flash_ok(x, window, kv):
        return _flash_attention_apply(params, x, positions, rope_theta,
                                      causal, window, softcap, lengths,
                                      return_kv)
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        if kv is None:
            k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
            v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
            k = rope(k, positions, rope_theta)
            kv_pos_eff = positions
        else:
            k, v = kv
            kv_pos_eff = kv_pos
        q = rope(q, positions, rope_theta)
        q = constrain_attn_q(q)
    k_valid = (None if lengths is None
               else jnp.arange(k.shape[1]) < lengths[:, None])
    with jax.named_scope("attn_core"):
        out = attend(q, k, v, positions, kv_pos_eff, causal=causal,
                     window=window, softcap=softcap, k_valid=k_valid)
    with jax.named_scope("attn_out"):
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if return_kv:
        return y, (k, v)
    return y


def _flash_attention_apply(params, x, positions, rope_theta, causal, window,
                           softcap, lengths, return_kv):
    """``attention_apply`` through the flash kernel: q, k and v come
    head-major out of their projections, so no transpose pass surrounds
    the kernel."""
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"])
        pos = positions[:, None]                       # (B, 1, S)
        q = constrain_act(rope(q, pos, rope_theta),
                          "batch", "heads", "seq", "head_dim")
        k = rope(k, pos, rope_theta)
    with jax.named_scope("attn_core"):
        # a window as wide as any sequence is none
        out = kops.flash_attention(
            q, k, v, lengths, causal=causal,
            window=0 if window >= (1 << 29) else window,
            softcap=float(softcap))
    with jax.named_scope("attn_out"):
        y = jnp.einsum("bhsk,hkd->bsd", out, params["wo"])
    if return_kv:
        return y, (k.swapaxes(1, 2), v.swapaxes(1, 2))
    return y


def cross_attention_kv(params: Params, enc_out: jnp.ndarray):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return k, v


def cross_attention_apply(params: Params, x: jnp.ndarray,
                          kv: Tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
    """Cross attention: queries from x, keys/values precomputed (no RoPE)."""
    B, S, _ = x.shape
    k, v = kv
    T = k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q = constrain_attn_q(q)
    zero_q = jnp.zeros((B, S), dtype=jnp.int32)
    zero_k = jnp.zeros((B, T), dtype=jnp.int32)
    out = attend(q, k, v, zero_q, zero_k, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# --------------------------------------------------------------------------
# gated MLP (SwiGLU)
# --------------------------------------------------------------------------
def mlp_init(key, d: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": _dense_init(k1, d, d_ff, dtype),
        "wg": _dense_init(k2, d, d_ff, dtype),
        "wo": _dense_init(k3, d_ff, d, dtype),
    }


def mlp_axes() -> Params:
    return {"wi": ("embed", "ffn"), "wg": ("embed", "ffn"), "wo": ("ffn", "embed")}


def mlp_apply(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jnp.einsum("bsd,df->bsf", x, params["wi"])
    g = jnp.einsum("bsd,df->bsf", x, params["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("bsf,fd->bsd", h, params["wo"])
