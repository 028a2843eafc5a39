"""Whisper-style encoder–decoder backbone.

The conv/mel frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed frame embeddings (B, S_enc, d_model) straight into the encoder.
Encoder layers are bidirectional self-attn + MLP; decoder layers are causal
self-attn + cross-attn + MLP.  Both stacks scan over stacked layer params.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain_act
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.transformer import (_gather_last, _stack_axes,
                                      _stack_init, scan_layers)


class EncDecLM:
    def __init__(self, cfg: ModelConfig):
        assert cfg.encoder_layers > 0
        self.cfg = cfg
        self.pdt = jnp.dtype(cfg.param_dtype)

    # ----------------------------------------------------------------- init
    def _enc_layer_init(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.rmsnorm_init(cfg.d_model, self.pdt),
            "attn": L.attention_init(k1, cfg.d_model, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.head_dim, self.pdt),
            "ln2": L.rmsnorm_init(cfg.d_model, self.pdt),
            "ffn": L.mlp_init(k2, cfg.d_model, cfg.d_ff, self.pdt),
        }

    def _dec_layer_init(self, key):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": L.rmsnorm_init(cfg.d_model, self.pdt),
            "attn": L.attention_init(k1, cfg.d_model, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.head_dim, self.pdt),
            "lnx": L.rmsnorm_init(cfg.d_model, self.pdt),
            "xattn": L.attention_init(k2, cfg.d_model, cfg.num_heads,
                                      cfg.num_kv_heads, cfg.head_dim, self.pdt),
            "ln2": L.rmsnorm_init(cfg.d_model, self.pdt),
            "ffn": L.mlp_init(k3, cfg.d_model, cfg.d_ff, self.pdt),
        }

    def init(self, key) -> Dict:
        cfg = self.cfg
        ke, k1, k2 = jax.random.split(key, 3)
        return {
            "embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model, self.pdt),
            "enc_layers": _stack_init(k1, cfg.encoder_layers,
                                      self._enc_layer_init),
            "enc_norm": L.rmsnorm_init(cfg.d_model, self.pdt),
            "dec_layers": _stack_init(k2, cfg.num_layers, self._dec_layer_init),
            "final_norm": L.rmsnorm_init(cfg.d_model, self.pdt),
        }

    def logical_axes(self) -> Dict:
        enc = {"ln1": L.rmsnorm_axes(), "attn": L.attention_axes(),
               "ln2": L.rmsnorm_axes(), "ffn": L.mlp_axes()}
        dec = {"ln1": L.rmsnorm_axes(), "attn": L.attention_axes(),
               "lnx": L.rmsnorm_axes(), "xattn": L.attention_axes(),
               "ln2": L.rmsnorm_axes(), "ffn": L.mlp_axes()}
        return {
            "embed": ("vocab", "embed"),
            "enc_layers": _stack_axes(enc),
            "enc_norm": L.rmsnorm_axes(),
            "dec_layers": _stack_axes(dec),
            "final_norm": L.rmsnorm_axes(),
        }

    # ---------------------------------------------------------------- encode
    def encode(self, params, frames: jnp.ndarray) -> jnp.ndarray:
        """frames: (B, S_enc, d_model) stub-frontend embeddings."""
        cfg = self.cfg
        x = frames.astype(cfg.activation_dtype)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(x, p):
            p = L.cast_layer_params(p, cfg.activation_dtype)
            x = constrain_act(x, "batch", "seq", "act_embed")
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            h = L.attention_apply(p["attn"], h, positions,
                                  rope_theta=cfg.rope_theta, causal=False)
            x = x + h
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            return x + L.mlp_apply(p["ffn"], h), None

        x, _ = scan_layers(body, x, params["enc_layers"], cfg.cost_unroll)
        return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    def _embed(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(cfg.activation_dtype)
        return x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

    def _logits(self, params, x):
        return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                          params["embed"].astype(jnp.float32))

    # --------------------------------------------------------------- forward
    def forward(self, params, tokens, *, frames, lengths=None,
                remat: bool = False,
                return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Teacher-forced pass: (frames, tokens) → (logits, aux=0)."""
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        x = self._embed(params, tokens)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(x, p):
            p = L.cast_layer_params(p, cfg.activation_dtype)
            x = constrain_act(x, "batch", "seq", "act_embed")
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            h = L.attention_apply(p["attn"], h, positions,
                                  rope_theta=cfg.rope_theta, causal=True,
                                  lengths=lengths)
            x = x + h
            h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
            kv = L.cross_attention_kv(p["xattn"], enc_out)
            x = x + L.cross_attention_apply(p["xattn"], h, kv)
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            return x + L.mlp_apply(p["ffn"], h), None

        body = jax.checkpoint(body) if remat else body
        x, _ = scan_layers(body, x, params["dec_layers"], cfg.cost_unroll)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, jnp.float32(0.0)
        return self._logits(params, x), jnp.float32(0.0)

    # ----------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int, dtype=None,
                   enc_len: int = 0) -> Dict:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.serve_param_dtype)
        enc_len = enc_len or max_len
        def self_cache(_):
            return {
                "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "slot_pos": jnp.full((batch, max_len), -1, jnp.int32),
            }
        Lc = cfg.num_layers
        return {
            "lengths": jnp.zeros((batch,), jnp.int32),
            "self": jax.vmap(self_cache)(jnp.arange(Lc)),
            "cross_k": jnp.zeros((Lc, batch, enc_len, cfg.num_kv_heads,
                                  cfg.head_dim), dtype),
            "cross_v": jnp.zeros((Lc, batch, enc_len, cfg.num_kv_heads,
                                  cfg.head_dim), dtype),
        }

    def cache_axes(self) -> Dict:
        kv = ("batch", "kv", "kv_heads", "head_dim")
        return {
            "lengths": ("batch",),
            "self": _stack_axes({"k": kv, "v": kv,
                                 "slot_pos": ("batch", "kv")}),
            "cross_k": ("layers",) + kv,
            "cross_v": ("layers",) + kv,
        }

    # ---------------------------------------------------------------- prefill
    def prefill(self, params, cache, tokens, lengths, *,
                frames) -> Tuple[Dict, jnp.ndarray]:
        """Encode frames, precompute cross K/V, run decoder over the prompt."""
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        x = self._embed(params, tokens)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        valid = positions < lengths[:, None]

        def body(x, xs):
            p, lc = xs
            p = L.cast_layer_params(p, cfg.activation_dtype)
            x = constrain_act(x, "batch", "seq", "act_embed")
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            y, (k, v) = L.attention_apply(
                p["attn"], h, positions, rope_theta=cfg.rope_theta, causal=True,
                lengths=lengths, return_kv=True)
            x = x + y
            W = lc["k"].shape[1]
            kc = lc["k"].at[:, :S].set(k.astype(lc["k"].dtype))
            vc = lc["v"].at[:, :S].set(v.astype(lc["v"].dtype))
            slot_pos = lc["slot_pos"].at[:, :S].set(
                jnp.where(valid, positions, -1))
            ck, cv = L.cross_attention_kv(p["xattn"], enc_out)
            h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
            x = x + L.cross_attention_apply(p["xattn"], h, (ck, cv))
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            x = x + L.mlp_apply(p["ffn"], h)
            return x, ({"k": kc, "v": vc, "slot_pos": slot_pos},
                       ck.astype(lc["k"].dtype), cv.astype(lc["v"].dtype))

        x, (new_self, ck, cv) = scan_layers(body, x,
                                            (params["dec_layers"],
                                             cache["self"]), cfg.cost_unroll)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        new_cache = {"lengths": lengths, "self": new_self,
                     "cross_k": ck, "cross_v": cv}
        return new_cache, _gather_last(self._logits(params, x), lengths)

    # ------------------------------------------------------------ decode step
    def decode_step(self, params, cache, tokens) -> Tuple[Dict, jnp.ndarray]:
        cfg = self.cfg
        x = self._embed(params, tokens[:, None])
        q_pos = cache["lengths"]
        B = x.shape[0]

        def body(x, xs):
            p, lc, ck, cv = xs
            p = L.cast_layer_params(p, cfg.activation_dtype)
            x = constrain_act(x, "batch", "seq", "act_embed")
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
            k_new = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
            v_new = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
            q = L.rope(q, q_pos[:, None], cfg.rope_theta)
            k_new = L.rope(k_new, q_pos[:, None], cfg.rope_theta)
            b = jnp.arange(B)
            kc = lc["k"].at[b, q_pos].set(k_new[:, 0].astype(lc["k"].dtype))
            vc = lc["v"].at[b, q_pos].set(v_new[:, 0].astype(lc["v"].dtype))
            slot_pos = lc["slot_pos"].at[b, q_pos].set(q_pos)
            out = L.attend(q, kc.astype(q.dtype), vc.astype(q.dtype),
                           q_pos[:, None], slot_pos, causal=True,
                           k_valid=slot_pos >= 0)
            x = x + jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
            h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
            x = x + L.cross_attention_apply(
                p["xattn"], h, (ck.astype(x.dtype), cv.astype(x.dtype)))
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            x = x + L.mlp_apply(p["ffn"], h)
            return x, {"k": kc, "v": vc, "slot_pos": slot_pos}

        x, new_self = scan_layers(body, x,
                                  (params["dec_layers"], cache["self"],
                                   cache["cross_k"], cache["cross_v"]),
                                  cfg.cost_unroll)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        new_cache = dict(cache, lengths=q_pos + 1, self=new_self)
        return new_cache, self._logits(params, x[:, 0])
