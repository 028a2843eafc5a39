"""Decoder-only LM covering the dense / MoE / SSM / hybrid families.

Layers are *stacked* (leading layer axis) and iterated with ``jax.lax.scan``
so the HLO stays O(one layer) regardless of depth — essential for fast
multi-pod lowering and for remat.  Heterogeneous hybrids (RecurrentGemma's
(rec, rec, local-attn) pattern) scan over stacked *periods* plus an
unrolled remainder.

Three entry points per model:
  forward(params, tokens, ...)             teacher-forced full-sequence pass
  prefill(params, cache, tokens, lengths)  fill KV/recurrent caches
  decode_step(params, cache, tokens)       one token per sequence

Caches carry per-sequence ``lengths`` so ragged/continuous batching works.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain_act
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.config import (ATTN_GLOBAL, ATTN_LOCAL, RGLRU, RWKV6,
                                 ModelConfig)

GLOBAL_WINDOW = 1 << 30


def _stack_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _stack_axes(axes_tree):
    return jax.tree.map(lambda a: ("layers",) + tuple(a),
                        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


def _gather_last(logits: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """logits: (B, S, V) → (B, V) at position lengths-1."""
    b = jnp.arange(logits.shape[0])
    return logits[b, jnp.maximum(lengths - 1, 0)]


def scan_layers(body, carry, xs, unroll: bool = False):
    """lax.scan, or a Python unroll in cost-accounting mode (cfg.cost_unroll)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


class DecoderLM:
    """Decoder-only LM; family behaviour is driven entirely by the config."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.kinds = cfg.layer_kinds()
        self.pdt = jnp.dtype(cfg.param_dtype)
        # hybrid layout: full periods scanned + remainder unrolled
        pat = cfg.layer_pattern
        self.period_len = len(pat)
        self.n_periods = cfg.num_layers // self.period_len
        self.tail_kinds = self.kinds[self.n_periods * self.period_len:]
        self.homogeneous = len(set(pat)) == 1 or set(pat) <= {ATTN_GLOBAL, ATTN_LOCAL}

    # ------------------------------------------------------------------ init
    def _layer_init(self, kind: str):
        cfg = self.cfg
        def init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            p: Dict[str, Any] = {"ln1": L.rmsnorm_init(cfg.d_model, self.pdt),
                                 "ln2": L.rmsnorm_init(cfg.d_model, self.pdt)}
            if kind in (ATTN_GLOBAL, ATTN_LOCAL):
                p["attn"] = L.attention_init(k1, cfg.d_model, cfg.num_heads,
                                             cfg.num_kv_heads, cfg.head_dim, self.pdt)
            elif kind == RGLRU:
                p["rec"] = rglru_lib.rglru_init(k1, cfg.d_model, cfg.rglru_d_rnn,
                                                self.pdt)
            elif kind == RWKV6:
                p["tm_cm"] = rwkv_lib.rwkv_init(k1, cfg.d_model, cfg.d_ff,
                                                cfg.rwkv_head_dim, self.pdt)
            if kind != RWKV6:  # rwkv's channel-mix is its FFN
                if cfg.is_moe:
                    p["ffn"] = moe_lib.moe_init(k2, cfg.d_model, cfg.d_ff,
                                                cfg.num_experts, self.pdt)
                else:
                    p["ffn"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, self.pdt)
            return p
        return init

    def _layer_axes(self, kind: str) -> Dict:
        cfg = self.cfg
        p: Dict[str, Any] = {"ln1": L.rmsnorm_axes(), "ln2": L.rmsnorm_axes()}
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            p["attn"] = L.attention_axes()
        elif kind == RGLRU:
            p["rec"] = rglru_lib.rglru_axes()
        elif kind == RWKV6:
            p["tm_cm"] = rwkv_lib.rwkv_axes()
        if kind != RWKV6:
            p["ffn"] = moe_lib.moe_axes() if cfg.is_moe else L.mlp_axes()
        return p

    def init(self, key) -> Dict:
        cfg = self.cfg
        ke, kl, kt = jax.random.split(key, 3)
        params: Dict[str, Any] = {
            "embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model, self.pdt),
            "final_norm": L.rmsnorm_init(cfg.d_model, self.pdt),
        }
        if self.homogeneous:
            params["layers"] = _stack_init(kl, cfg.num_layers,
                                           self._layer_init(self.kinds[0]))
            # attention sub-params identical across kinds in {global, local}
        else:
            def period_init(key):
                keys = jax.random.split(key, self.period_len)
                return {f"l{i}": self._layer_init(self.cfg.layer_pattern[i])(keys[i])
                        for i in range(self.period_len)}
            params["periods"] = _stack_init(kl, self.n_periods, period_init)
            tails = {}
            tkeys = jax.random.split(kt, max(len(self.tail_kinds), 1))
            for i, kind in enumerate(self.tail_kinds):
                tails[f"t{i}"] = self._layer_init(kind)(tkeys[i])
            params["tail"] = tails
        return params

    def logical_axes(self) -> Dict:
        axes: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "final_norm": L.rmsnorm_axes(),
        }
        if self.homogeneous:
            axes["layers"] = _stack_axes(self._layer_axes(self.kinds[0]))
        else:
            period = {f"l{i}": self._layer_axes(self.cfg.layer_pattern[i])
                      for i in range(self.period_len)}
            axes["periods"] = _stack_axes(period)
            axes["tail"] = {f"t{i}": self._layer_axes(kind)
                            for i, kind in enumerate(self.tail_kinds)}
        return axes

    # ------------------------------------------------------------- embeddings
    def _embed(self, params, tokens, prefix_embeds=None):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(cfg.activation_dtype)
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.activation_dtype)
            if prefix_embeds is not None:
                x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
            return constrain_act(x, "batch", "seq", "act_embed")

    def _logits(self, params, x):
        cfg = self.cfg
        logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                            params["embed"].astype(jnp.float32))
        if cfg.final_logit_softcap:
            logits = L._softcap(logits, cfg.final_logit_softcap)
        return logits

    # -------------------------------------------------------- full-seq blocks
    def _attn_block(self, p, x, positions, window, valid):
        cfg = self.cfg
        with jax.named_scope("attn_qkv"):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        h = L.attention_apply(
            p["attn"], h, positions, rope_theta=cfg.rope_theta, causal=True,
            window=window, softcap=cfg.attn_logit_softcap,
            k_valid=valid)
        with jax.named_scope("attn_out"):
            return x + h

    def _ffn_block(self, p, x):
        cfg = self.cfg
        with jax.named_scope("moe" if cfg.is_moe else "mlp"):
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            if cfg.is_moe:
                h, aux = moe_lib.moe_apply(
                    p["ffn"], h, num_experts=cfg.num_experts,
                    k=cfg.experts_per_token,
                    capacity_factor=cfg.moe_capacity_factor, return_aux=True)
                return x + h, aux
            return x + L.mlp_apply(p["ffn"], h), jnp.float32(0.0)

    def _layer_seq(self, kind, p, x, positions, window, valid, rec_state):
        """One layer over a full sequence. Returns (x, aux, new_rec_state)."""
        cfg = self.cfg
        p = L.cast_layer_params(p, cfg.activation_dtype)
        x = constrain_act(x, "batch", "seq", "act_embed")
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            x = self._attn_block(p, x, positions, window, valid)
            x, aux = self._ffn_block(p, x)
            return x, aux, rec_state
        if kind == RGLRU:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rglru"):
                h, new_state = rglru_lib.rglru_block_seq(p["rec"], h, rec_state)
            x = x + h
            x, aux = self._ffn_block(p, x)
            return x, aux, new_state
        if kind == RWKV6:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_time_mix"):
                h, tm_state = rwkv_lib.time_mix_seq(
                    p["tm_cm"], h, cfg.rwkv_head_dim, rec_state["tm"])
            x = x + h
            h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_channel_mix"):
                h2, cm_state = rwkv_lib.channel_mix_seq(p["tm_cm"], h2,
                                                        rec_state["cm"])
            return x + h2, jnp.float32(0.0), {"tm": tm_state, "cm": cm_state}
        raise ValueError(kind)

    # --------------------------------------------------------------- forward
    def forward(self, params, tokens, *, prefix_embeds=None, lengths=None,
                remat: bool = False,
                return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Teacher-forced pass → (logits (B,S,V), aux_loss scalar).

        ``return_hidden=True`` returns the final-norm hidden states instead
        of logits so the caller can do a vocab-chunked cross-entropy (the
        full (B,S,V) logits tensor is prohibitive for 256k vocabs).
        """
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        valid = (positions < lengths[:, None]) if lengths is not None else None

        if self.homogeneous:
            kind0 = self.kinds[0]
            windows = jnp.asarray(
                [cfg.local_window if k == ATTN_LOCAL else GLOBAL_WINDOW
                 for k in self.kinds], dtype=jnp.int32)
            if kind0 == RWKV6:
                states = jax.vmap(
                    lambda _: rwkv_lib.init_state(B, cfg.d_model,
                                                  cfg.rwkv_head_dim,
                                                  cfg.activation_dtype)
                )(jnp.arange(cfg.num_layers))
                def body(carry, xs):
                    x, aux = carry
                    p, st = xs
                    x, a, _ = self._layer_seq(RWKV6, p, x, positions,
                                              GLOBAL_WINDOW, valid, st)
                    return (x, aux + a), None
                body = jax.checkpoint(body) if remat else body
                (x, aux), _ = scan_layers(body, (x, jnp.float32(0.0)),
                                          (params["layers"], states),
                                          cfg.cost_unroll)
            else:
                def body(carry, xs):
                    x, aux = carry
                    p, w = xs
                    p = L.cast_layer_params(p, cfg.activation_dtype)
                    x = constrain_act(x, "batch", "seq", "act_embed")
                    x = self._attn_block(p, x, positions, w, valid)
                    x, a = self._ffn_block(p, x)
                    return (x, aux + a), None
                body = jax.checkpoint(body) if remat else body
                (x, aux), _ = scan_layers(body, (x, jnp.float32(0.0)),
                                          (params["layers"], windows),
                                          cfg.cost_unroll)
        else:
            x, aux = self._forward_hybrid(params, x, positions, valid, remat)

        with jax.named_scope("logits"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            if return_hidden:
                return x, aux
            return self._logits(params, x), aux

    def _forward_hybrid(self, params, x, positions, valid, remat):
        cfg = self.cfg
        B = x.shape[0]
        def fresh_state(kind):
            if kind == RGLRU:
                return rglru_lib.init_state(B, cfg.rglru_d_rnn,
                                            cfg.activation_dtype)
            return None
        def period_body(carry, p):
            x, aux = carry
            for i, kind in enumerate(cfg.layer_pattern):
                w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
                x, a, _ = self._layer_seq(kind, p[f"l{i}"], x, positions, w,
                                          valid, fresh_state(kind))
                aux = aux + a
            return (x, aux), None
        body = jax.checkpoint(period_body) if remat else period_body
        (x, aux), _ = scan_layers(body, (x, jnp.float32(0.0)), params["periods"],
                                  cfg.cost_unroll)
        for i, kind in enumerate(self.tail_kinds):
            w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
            x, a, _ = self._layer_seq(kind, params["tail"][f"t{i}"], x,
                                      positions, w, valid, fresh_state(kind))
            aux = aux + a
        return x, aux

    # ----------------------------------------------------------------- cache
    def _attn_cache_len(self, kind: str, max_len: int) -> int:
        if kind == ATTN_LOCAL and self.cfg.local_window:
            return min(self.cfg.local_window, max_len)
        return max_len

    def _layer_cache(self, kind: str, batch: int, max_len: int, dtype):
        cfg = self.cfg
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            W = self._attn_cache_len(kind, max_len)
            return {
                "k": jnp.zeros((batch, cfg.num_kv_heads, W, cfg.head_dim), dtype),
                "v": jnp.zeros((batch, cfg.num_kv_heads, W, cfg.head_dim), dtype),
                "slot_pos": jnp.full((batch, W), -1, jnp.int32),
            }
        if kind == RGLRU:
            return rglru_lib.init_state(batch, cfg.rglru_d_rnn, dtype)
        if kind == RWKV6:
            return rwkv_lib.init_state(batch, cfg.d_model, cfg.rwkv_head_dim, dtype)
        raise ValueError(kind)

    def init_cache(self, batch: int, max_len: int, dtype=None) -> Dict:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.serve_param_dtype)
        cache: Dict[str, Any] = {"lengths": jnp.zeros((batch,), jnp.int32)}
        if self.homogeneous:
            # uniform cache length across layers keeps the stack scannable;
            # mixed local/global dense archs pay full length on local layers.
            kind = (ATTN_LOCAL if set(self.kinds) == {ATTN_LOCAL} else
                    (RWKV6 if self.kinds[0] == RWKV6 else ATTN_GLOBAL))
            cache["layers"] = jax.vmap(
                lambda _: self._layer_cache(kind, batch, max_len, dtype)
            )(jnp.arange(cfg.num_layers))
        else:
            def period_cache(_):
                return {f"l{i}": self._layer_cache(cfg.layer_pattern[i], batch,
                                                   max_len, dtype)
                        for i in range(self.period_len)}
            cache["periods"] = jax.vmap(period_cache)(jnp.arange(self.n_periods))
            cache["tail"] = {f"t{i}": self._layer_cache(kind, batch, max_len, dtype)
                             for i, kind in enumerate(self.tail_kinds)}
        return cache

    def _layer_cache_axes(self, kind: str):
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            return {"k": ("batch", "kv_heads", "kv", "head_dim"),
                    "v": ("batch", "kv_heads", "kv", "head_dim"),
                    "slot_pos": ("batch", "kv")}
        if kind == RGLRU:
            return {"s": ("batch", "rnn"),
                    "conv": ("batch", None, "rnn")}
        if kind == RWKV6:
            return {"tm": {"shift": ("batch", "act_embed"),
                           "wkv": ("batch", "heads", None, None)},
                    "cm": ("batch", "act_embed")}
        raise ValueError(kind)

    def cache_axes(self) -> Dict:
        """Logical sharding axes mirroring init_cache's structure."""
        cfg = self.cfg
        axes: Dict[str, Any] = {"lengths": ("batch",)}
        if self.homogeneous:
            kind = (RWKV6 if self.kinds[0] == RWKV6 else
                    (ATTN_LOCAL if set(self.kinds) == {ATTN_LOCAL}
                     else ATTN_GLOBAL))
            axes["layers"] = _stack_axes(self._layer_cache_axes(kind))
        else:
            period = {f"l{i}": self._layer_cache_axes(cfg.layer_pattern[i])
                      for i in range(self.period_len)}
            axes["periods"] = _stack_axes(period)
            axes["tail"] = {f"t{i}": self._layer_cache_axes(kind)
                            for i, kind in enumerate(self.tail_kinds)}
        return axes

    # --------------------------------------------------- cached attention ops
    def _attn_prefill(self, p, x, positions, window, valid, lc):
        """Self-attn over the prompt, writing into an (unrotated) cache."""
        cfg = self.cfg
        with jax.named_scope("attn_qkv"):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        y, (k, v) = L.attention_apply(
            p["attn"], h, positions, rope_theta=cfg.rope_theta, causal=True,
            window=window, softcap=cfg.attn_logit_softcap, k_valid=valid,
            return_kv=True)
        with jax.named_scope("kv_write"):
            lc = self._write_prompt_kv(k, v, positions, valid, lc)
        with jax.named_scope("attn_out"):
            return x + y, lc

    @staticmethod
    def _write_prompt_kv(k, v, positions, valid, lc):
        """The prompt's keys and values, (B, S, K, hd), into the layer's
        head-major cache, (B, K, W, hd)."""
        W = lc["k"].shape[2]
        B, S = positions.shape
        head_major = lambda a: a.transpose(0, 2, 1, 3).astype(lc["k"].dtype)
        if W >= S:
            kc = lc["k"].at[:, :, :S].set(head_major(k))
            vc = lc["v"].at[:, :, :S].set(head_major(v))
            pos = positions
            slot_pos = lc["slot_pos"].at[:, :S].set(
                jnp.where(valid if valid is not None else jnp.ones_like(pos, bool),
                          pos, -1))
        else:
            # Ring buffer: slot s must hold the *latest valid* position
            # p ≡ s (mod W).  A gather (one winner per slot) avoids the
            # unordered-duplicate-scatter hazard:
            #   p(s) = len-1 − ((len-1−s) mod W)
            lens = (valid.sum(axis=1).astype(jnp.int32) if valid is not None
                    else jnp.full((B,), S, jnp.int32))
            s_idx = jnp.arange(W)[None, :]                       # (1, W)
            last = lens[:, None] - 1 - ((lens[:, None] - 1 - s_idx) % W)
            ok = (last >= 0) & (lens[:, None] > 0)
            gidx = jnp.clip(last, 0, S - 1)
            b = jnp.arange(B)[:, None]
            kc = head_major(k[b, gidx])
            vc = head_major(v[b, gidx])
            slot_pos = jnp.where(ok, last, -1)
        return {"k": kc, "v": vc, "slot_pos": slot_pos}

    @staticmethod
    def _decode_visible(slot_pos, q_pos):
        """(B, W): the live slots a decode step attends to, less the slot
        each row's new entry takes (in a ring buffer, the entry it
        evicts)."""
        W = slot_pos.shape[-1]
        return (slot_pos >= 0) & (jnp.arange(W) != (q_pos % W)[:, None])

    def _attn_decode(self, p, x, q_pos, window, kv):
        """One-token attention against a layer's cache, which it only
        reads; x: (B, 1, D).  ``kv``: "k"/"v" (B, K, W, hd); "slot_pos" and
        "visible" (``_decode_visible``), (B, W).  Returns x and the new
        (B, K, hd) key and value, for the caller to write."""
        cfg = self.cfg
        with jax.named_scope("attn_qkv"):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
            k_new = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
            v_new = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
            q = L.rope(q, q_pos[:, None], cfg.rope_theta)
            k_new = L.rope(k_new, q_pos[:, None], cfg.rope_theta)
            k_new = k_new[:, 0].astype(kv["k"].dtype)
            v_new = v_new[:, 0].astype(kv["v"].dtype)
        with jax.named_scope("attn_core"):
            out = L.attend_cached(q, kv["k"], kv["v"], k_new, v_new, q_pos,
                                  kv["slot_pos"], window=window,
                                  softcap=cfg.attn_logit_softcap,
                                  k_valid=kv["visible"])
        with jax.named_scope("attn_out"):
            y = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
            return x + y, (k_new, v_new)

    @staticmethod
    def _write_decode_kv(lc, k_new, v_new, q_pos):
        """Each row's new key, value and position into its slot of a cache
        of one layer, (B, K, W, hd), or of the stack, (L, B, K, W, hd), with
        new entries (B, K, hd) or (L, B, K, hd).  One dynamic_update_slice
        per row and tensor, in place when the cache is donated: a scatter
        here makes XLA re-lay the whole stack."""
        lead = lc["k"].ndim - 4
        zeros = (0,) * lead
        slot = q_pos % lc["slot_pos"].shape[-1]
        k, v, pos = lc["k"], lc["v"], lc["slot_pos"]
        for b in range(q_pos.shape[0]):
            at = zeros + (b, 0, slot[b], 0)
            k = jax.lax.dynamic_update_slice(
                k, k_new[..., b:b + 1, :, None, :], at)
            v = jax.lax.dynamic_update_slice(
                v, v_new[..., b:b + 1, :, None, :], at)
            pos = jax.lax.dynamic_update_slice(
                pos, jnp.full(pos.shape[:lead] + (1, 1), q_pos[b]),
                zeros + (b, slot[b]))
        return {"k": k, "v": v, "slot_pos": pos}

    # ---------------------------------------------------------------- prefill
    def _layer_prefill(self, kind, p, x, positions, window, valid, lc):
        cfg = self.cfg
        p = L.cast_layer_params(p, cfg.activation_dtype)
        x = constrain_act(x, "batch", "seq", "act_embed")
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            x, lc = self._attn_prefill(p, x, positions, window, valid, lc)
            x, _ = self._ffn_block(p, x)
            return x, lc
        if kind == RGLRU:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rglru"):
                h, lc = rglru_lib.rglru_block_seq(p["rec"], h, lc, valid=valid)
            x = x + h
            x, _ = self._ffn_block(p, x)
            return x, lc
        if kind == RWKV6:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_time_mix"):
                h, tm = rwkv_lib.time_mix_seq(p["tm_cm"], h, cfg.rwkv_head_dim,
                                              lc["tm"], valid=valid)
            x = x + h
            h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_channel_mix"):
                h2, cm = rwkv_lib.channel_mix_seq(p["tm_cm"], h2, lc["cm"],
                                                  valid=valid)
            return x + h2, {"tm": tm, "cm": cm}
        raise ValueError(kind)

    def prefill(self, params, cache, tokens, lengths,
                prefix_embeds=None) -> Tuple[Dict, jnp.ndarray]:
        """Process prompts (right-padded to S) → (cache, last-token logits)."""
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        valid = positions < lengths[:, None]

        if self.homogeneous:
            windows = jnp.asarray(
                [cfg.local_window if k == ATTN_LOCAL else GLOBAL_WINDOW
                 for k in self.kinds], dtype=jnp.int32)
            kind0 = RWKV6 if self.kinds[0] == RWKV6 else ATTN_GLOBAL
            def body(x, xs):
                p, w, lc = xs
                x, lc = self._layer_prefill(
                    self.kinds[0] if kind0 == RWKV6 else ATTN_GLOBAL,
                    p, x, positions, w, valid, lc)
                return x, lc
            x, new_layers = scan_layers(body, x,
                                        (params["layers"], windows,
                                         cache["layers"]), cfg.cost_unroll)
            new_cache = {"lengths": lengths, "layers": new_layers}
        else:
            def body(x, xs):
                p, lc = xs
                new_lc = {}
                for i, kind in enumerate(cfg.layer_pattern):
                    w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
                    x, new_lc[f"l{i}"] = self._layer_prefill(
                        kind, p[f"l{i}"], x, positions, w, valid, lc[f"l{i}"])
                return x, new_lc
            x, new_periods = scan_layers(body, x,
                                         (params["periods"],
                                          cache["periods"]), cfg.cost_unroll)
            new_tail = {}
            for i, kind in enumerate(self.tail_kinds):
                w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
                x, new_tail[f"t{i}"] = self._layer_prefill(
                    kind, params["tail"][f"t{i}"], x, positions, w, valid,
                    cache["tail"][f"t{i}"])
            new_cache = {"lengths": lengths, "periods": new_periods,
                         "tail": new_tail}

        with jax.named_scope("logits"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return new_cache, _gather_last(self._logits(params, x), lengths)

    # ------------------------------------------------------------ decode step
    def _layer_decode(self, kind, p, x, q_pos, window, lc):
        """One layer's decode step → (x, new state); an attention layer
        only reads its cache and returns its new key and value instead."""
        cfg = self.cfg
        p = L.cast_layer_params(p, cfg.activation_dtype)
        x = constrain_act(x, "batch", "seq", "act_embed")
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            x, kv_new = self._attn_decode(p, x, q_pos, window, lc)
            x, _ = self._ffn_block(p, x)
            return x, kv_new
        if kind == RGLRU:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rglru"):
                h1, lc = rglru_lib.rglru_block_step(p["rec"], h[:, 0], lc)
            x = x + h1[:, None]
            x, _ = self._ffn_block(p, x)
            return x, lc
        if kind == RWKV6:
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_time_mix"):
                h1, tm = rwkv_lib.time_mix_step(p["tm_cm"], h[:, 0],
                                                cfg.rwkv_head_dim, lc["tm"])
            x = x + h1[:, None]
            h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            with jax.named_scope("rwkv_channel_mix"):
                h2s, cm = rwkv_lib.channel_mix_step(p["tm_cm"], h2[:, 0],
                                                    lc["cm"])
            return x + h2s[:, None], {"tm": tm, "cm": cm}
        raise ValueError(kind)

    def decode_step(self, params, cache, tokens) -> Tuple[Dict, jnp.ndarray]:
        """tokens: (B,) next input token per sequence → (cache, logits (B,V))."""
        cfg = self.cfg
        x = self._embed(params, tokens[:, None])
        q_pos = cache["lengths"]

        if self.homogeneous:
            if self.kinds[0] == RWKV6:
                # the recurrent state is rewritten whole in every layer
                def body(x, xs):
                    p, lc = xs
                    return self._layer_decode(RWKV6, p, x, q_pos,
                                              GLOBAL_WINDOW, lc)
                x, new_layers = scan_layers(body, x,
                                            (params["layers"],
                                             cache["layers"]), cfg.cost_unroll)
            else:
                # the stacked cache is only read inside the scan; each
                # layer's new key and value come out as the scan's ys and
                # are written once, in place, after it
                windows = jnp.asarray(
                    [cfg.local_window if k == ATTN_LOCAL else GLOBAL_WINDOW
                     for k in self.kinds], dtype=jnp.int32)
                kv = cache["layers"]
                slot_pos = kv["slot_pos"][0]    # the same in every layer
                visible = self._decode_visible(slot_pos, q_pos)
                def body(x, xs):
                    p, w, k, v = xs
                    lc = {"k": k, "v": v, "slot_pos": slot_pos,
                          "visible": visible}
                    return self._layer_decode(ATTN_GLOBAL, p, x, q_pos, w, lc)
                x, (k_new, v_new) = scan_layers(
                    body, x, (params["layers"], windows, kv["k"], kv["v"]),
                    cfg.cost_unroll)
                with jax.named_scope("kv_write"):
                    new_layers = self._write_decode_kv(kv, k_new, v_new, q_pos)
            new_cache = {"lengths": q_pos + 1, "layers": new_layers}
        else:
            def layer(kind, p, x, w, lc):
                if kind not in (ATTN_GLOBAL, ATTN_LOCAL):
                    return self._layer_decode(kind, p, x, q_pos, w, lc)
                visible = self._decode_visible(lc["slot_pos"], q_pos)
                x, out = self._layer_decode(kind, p, x, q_pos, w,
                                            dict(lc, visible=visible))
                with jax.named_scope("kv_write"):
                    return x, self._write_decode_kv(lc, *out, q_pos)

            def body(x, xs):
                p, lc = xs
                new_lc = {}
                for i, kind in enumerate(cfg.layer_pattern):
                    w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
                    x, new_lc[f"l{i}"] = layer(kind, p[f"l{i}"], x, w,
                                               lc[f"l{i}"])
                return x, new_lc
            x, new_periods = scan_layers(body, x,
                                         (params["periods"],
                                          cache["periods"]), cfg.cost_unroll)
            new_tail = {}
            for i, kind in enumerate(self.tail_kinds):
                w = cfg.local_window if kind == ATTN_LOCAL else GLOBAL_WINDOW
                x, new_tail[f"t{i}"] = layer(kind, params["tail"][f"t{i}"], x,
                                             w, cache["tail"][f"t{i}"])
            new_cache = {"lengths": q_pos + 1, "periods": new_periods,
                         "tail": new_tail}

        with jax.named_scope("logits"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return new_cache, self._logits(params, x[:, 0])
