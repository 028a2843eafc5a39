"""Capacity-based top-k Mixture-of-Experts FFN (GShard/Switch-style).

Dispatch is scatter-based (no (S, E, C) one-hot blowup): every token's k
assignments get a position-in-expert via a cumulative sum, tokens beyond
an expert's capacity are dropped (weight renormalised), and activations
are scattered into an (E, C, d) buffer that the expert matmuls consume.

Distribution — three modes, selected by the active sharding rules:

  gspmd (default)   scatter/gather wrapped in ``shard_map`` over the batch
                    axes (GSPMD partitions a scatter-add by splitting the
                    updates over the model axis and all-reducing partial
                    multi-GB buffers — measured: the whole MoE family was
                    collective-bound at <1% MFU); the expert matmuls stay
                    in GSPMD-land so ffn-TP / expert-EP rules apply (dbrx).

  local             rules map "moe_local" → whole MoE block inside
                    ``shard_map`` over (batch[, seq via "moe_seq"→model])
                    with expert weights replicated — zero collectives in
                    the block.  Right for small-expert MoE (granite-moe:
                    d_ff=512, expert weights ~190 MB).  With "moe_seq" the
                    dispatch is per-sequence-shard (GShard grouping), i.e.
                    capacity is enforced per group.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd


def moe_init(key, d: int, d_ff: int, num_experts: int, dtype) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    def w(k, shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)
    return {
        "router": w(k1, (d, num_experts)),
        "wi": w(k2, (num_experts, d, d_ff)),
        "wg": w(k3, (num_experts, d, d_ff)),
        "wo": (jax.random.normal(k4, (num_experts, d_ff, d), dtype=jnp.float32)
               * (1.0 / math.sqrt(d_ff))).astype(dtype),
    }


def moe_axes() -> Dict:
    return {
        "router": ("embed", "expert"),
        "wi": ("expert", "embed", "ffn"),
        "wg": ("expert", "embed", "ffn"),
        "wo": ("expert", "ffn", "embed"),
    }


def _capacity(tokens: int, num_experts: int, k: int, factor: float) -> int:
    cap = int(math.ceil(tokens * k / num_experts * factor))
    return max(cap, k)


# --------------------------------------------------------------------------
# the pure per-shard MoE math (works on whatever (B, S, d) slice it sees)
# --------------------------------------------------------------------------
def _route(params, x, E, k):
    gates = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                   params["router"].astype(jnp.float32)), axis=-1)
    topk_w, topk_idx = jax.lax.top_k(gates, k)
    topk_w = topk_w / jnp.clip(topk_w.sum(-1, keepdims=True), 1e-9)
    return gates, topk_w, topk_idx


def _dispatch_indices(topk_idx, E, C):
    B, S, k = topk_idx.shape
    onehot = jax.nn.one_hot(topk_idx.reshape(B, S * k), E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - 1
    pos = jnp.take_along_axis(
        pos, topk_idx.reshape(B, S * k)[..., None], axis=-1)[..., 0]
    keep = pos < C
    return onehot, jnp.where(keep, pos, 0), keep


def _scatter_local(xk, eidx, pos, *, E: int, C: int):
    B = xk.shape[0]
    b = jnp.broadcast_to(jnp.arange(B)[:, None], eidx.shape)
    buf = jnp.zeros((B, E, C, xk.shape[-1]), xk.dtype)
    return buf.at[b, eidx, pos].add(xk)


def _gather_local(buf, eidx, pos):
    B = buf.shape[0]
    b = jnp.broadcast_to(jnp.arange(B)[:, None], eidx.shape)
    return buf[b, eidx, pos]


def _expert_ffn(params, buf):
    h = jnp.einsum("becd,edf->becf", buf, params["wi"])
    g = jnp.einsum("becd,edf->becf", buf, params["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("becf,efd->becd", h, params["wo"])


def _moe_core(params, x, *, E: int, k: int, capacity_factor: float):
    """Full MoE block on a local (B, S, d) slice — no collectives."""
    B, S, d = x.shape
    C = _capacity(S, E, k, capacity_factor)
    _, topk_w, topk_idx = _route(params, x, E, k)
    _, pos, keep = _dispatch_indices(topk_idx, E, C)
    eidx = topk_idx.reshape(B, S * k)
    xk = jnp.where(keep[..., None], jnp.repeat(x, k, axis=1), 0)
    buf = _scatter_local(xk, eidx, pos, E=E, C=C)
    out_buf = _expert_ffn(params, buf)
    yk = _gather_local(out_buf, eidx, pos)
    w = (topk_w.reshape(B, S * k) * keep).astype(x.dtype)
    return (yk * w[..., None]).reshape(B, S, k, d).sum(axis=2)


def _aux_loss(params, x, E, k):
    gates, _, topk_idx = _route(params, x, E, k)
    B, S, _ = topk_idx.shape
    onehot = jax.nn.one_hot(topk_idx.reshape(B, S * k), E, dtype=jnp.float32)
    me = gates.mean(axis=(0, 1))
    ce = (onehot.sum(axis=1) / (S * k)).mean(axis=0)
    return E * jnp.sum(me * ce)


# --------------------------------------------------------------------------
# distribution modes
# --------------------------------------------------------------------------
def _mesh_mode(B: int, Sk: int, E: int):
    """Resolve (mesh, batch_axes, mode, seq_axis) from the active rules."""
    ctx = shd._ACT_CTX[0]
    if ctx is None:
        return None
    mesh, rules = ctx
    sizes = dict(mesh.shape)
    b_axes = tuple(a for a in ("pod", "data") if a in sizes)
    n_b = math.prod(sizes[a] for a in b_axes) if b_axes else 1
    if not b_axes or B % n_b:
        return None
    if (rules.get("moe_ep_local") and "model" in sizes
            and E % sizes["model"] == 0):
        mode = "ep_local"
    elif not rules.get("ffn") and not rules.get("expert"):
        mode = "local"
    else:
        mode = "gspmd"
    seq_ok = (rules.get("moe_seq") and "model" in sizes
              and Sk % sizes["model"] == 0)
    return mesh, b_axes, mode, ("model" if mode == "local" and seq_ok else None)


def _moe_ep_local(params, x, *, E: int, k: int, capacity_factor: float,
                  mesh, b_axes):
    """Expert-parallel local dispatch: every model shard owns E/m experts,
    routes its (replicated) tokens to its own experts locally, and the
    combined outputs are summed with ONE psum of (B, S, d) per layer —
    instead of GSPMD's multi-GB partial-buffer all-reduces."""
    m = dict(mesh.shape)["model"]
    E_l = E // m

    def block(p, x_l):
        B_l, S, d = x_l.shape
        C = _capacity(S, E, k, capacity_factor)
        _, topk_w, topk_idx = _route(p, x_l, E, k)     # router is replicated
        _, pos, keep = _dispatch_indices(topk_idx, E, C)
        eidx = topk_idx.reshape(B_l, S * k)
        first = jax.lax.axis_index("model") * E_l
        mine = keep & (eidx >= first) & (eidx < first + E_l)
        xk = jnp.where(mine[..., None], jnp.repeat(x_l, k, axis=1), 0)
        e_loc = jnp.where(mine, eidx - first, 0)
        p_loc = jnp.where(mine, pos, 0)
        buf = _scatter_local(xk, e_loc, p_loc, E=E_l, C=C)
        out_buf = _expert_ffn(p, buf)
        yk = _gather_local(out_buf, e_loc, p_loc)
        w = (topk_w.reshape(B_l, S * k) * mine).astype(x_l.dtype)
        y = (yk * w[..., None]).reshape(B_l, S, k, d).sum(axis=2)
        return jax.lax.psum(y, "model")

    pspec = {"router": P(), "wi": P("model", None, None),
             "wg": P("model", None, None), "wo": P("model", None, None)}
    xspec = P(b_axes, None, None)
    return jax.shard_map(block, mesh=mesh, in_specs=(pspec, xspec),
                         out_specs=xspec, check_vma=False)(params, x)


def moe_apply(params: Dict, x: jnp.ndarray, *, num_experts: int, k: int,
              capacity_factor: float = 1.25,
              return_aux: bool = False):
    """x: (B, S, d) → (B, S, d) plus optional load-balancing aux loss."""
    B, S, d = x.shape
    E = num_experts
    mode = _mesh_mode(B, S, E)
    core = functools.partial(_moe_core, E=E, k=k,
                             capacity_factor=capacity_factor)

    if mode is None:
        y = core(params, x)
    else:
        mesh, b_axes, kind, seq_ax = mode
        if kind == "ep_local":
            y = _moe_ep_local(params, x, E=E, k=k,
                              capacity_factor=capacity_factor,
                              mesh=mesh, b_axes=b_axes)
        elif kind == "local":
            # whole block local per (batch[, seq]) shard; weights replicated
            xspec = P(b_axes, seq_ax, None)
            y = jax.shard_map(core, mesh=mesh,
                              in_specs=(P(), xspec), out_specs=xspec,
                              check_vma=False)(params, x)
        else:
            # dispatch local, expert matmuls under GSPMD (TP/EP rules)
            C = _capacity(S, E, k, capacity_factor)
            _, topk_w, topk_idx = _route(params, x, E, k)
            _, pos, keep = _dispatch_indices(topk_idx, E, C)
            eidx = topk_idx.reshape(B, S * k)
            xk = jnp.where(keep[..., None], jnp.repeat(x, k, axis=1), 0)
            spec3, spec2 = P(b_axes, None, None), P(b_axes, None)
            spec4 = P(b_axes, None, None, None)
            scatter = functools.partial(_scatter_local, E=E, C=C)
            buf = jax.shard_map(scatter, mesh=mesh,
                                in_specs=(spec3, spec2, spec2),
                                out_specs=spec4, check_vma=False)(xk, eidx, pos)
            out_buf = _expert_ffn(params, buf)
            yk = jax.shard_map(_gather_local, mesh=mesh,
                               in_specs=(spec4, spec2, spec2), out_specs=spec3,
                               check_vma=False)(out_buf, eidx, pos)
            w = (topk_w.reshape(B, S * k) * keep).astype(x.dtype)
            y = (yk * w[..., None]).reshape(B, S, k, d).sum(axis=2)

    if not return_aux:
        return y
    return y, _aux_loss(params, x, E, k)
