"""Which attention path a layer takes, and that both give one answer.

Self-attention takes the flash kernel where the kernels are compiled for a
chip and the shapes allow; on the CPU the suite forces that path by
patching the backend check, and the kernel runs interpreted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.kernels import ops as kops
from repro.models import build_model, reduced
from repro.models import layers as L
from repro.models.transformer import GLOBAL_WINDOW


def _prefill(model, params, tokens, lengths):
    cache = model.init_cache(tokens.shape[0], tokens.shape[1] + 8)
    return model.prefill(params, cache, tokens, lengths)


@pytest.mark.parametrize("name", ["granite-3-2b", "granite-4.0-h-micro"])
def test_flash_path_matches_xla(name, monkeypatch):
    """The prefill through the kernel (forced) equals the one through
    ``attend``: the last position's logits and every live cache slot."""
    cfg = reduced(ARCHS[name])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (3, 256), 0,
                                cfg.vocab_size)
    lengths = jnp.asarray([256, 130, 77], jnp.int32)
    ref_cache, ref = _prefill(model, params, tokens, lengths)

    calls = []
    kernel = kops.flash_attention
    monkeypatch.setattr(L, "interpret_mode", lambda: False)
    monkeypatch.setattr(kops, "flash_attention",
                        lambda *a, **kw: calls.append(1) or kernel(*a, **kw))
    cache, out = _prefill(model, params, tokens, lengths)
    assert calls, "the kernel path was not taken"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    live = np.arange(tokens.shape[1] + 8) < np.asarray(lengths)[:, None]
    slots = 0
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(cache),
                                 jax.tree.leaves(ref_cache)):
        if path[-1].key not in ("k", "v"):
            continue
        # (..., B, K, W, hd): compare each row's live slots
        got = np.moveaxis(np.asarray(got), -3, -2)[..., live, :, :]
        want = np.moveaxis(np.asarray(want), -3, -2)[..., live, :, :]
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        slots += 1
    assert slots >= 2


@pytest.mark.parametrize("chip,seq_len,window,kv,takes", [
    (True, 256, 0, None, True),
    (True, 2048, GLOBAL_WINDOW, None, True),
    (True, 768, 64, None, True),
    (False, 256, 0, None, False),
    (True, 256, jnp.int32(64), None, False),
    (True, 200, 0, None, False),
    (True, 256, 0, "cache", False),
], ids=["chip", "global", "static_local", "cpu", "traced_window",
        "unaligned_seq", "kv_given"])
def test_ragged_masks_fall_back_to_xla(chip, seq_len, window, kv, takes,
                                       monkeypatch):
    """The kernel is taken on a chip, for self-attention with a static
    window over a sequence its blocks tile, right-padded rows included;
    every other case falls back to ``attend``.  (A program over several
    devices: ``test_tpu_compile.py``.)"""
    monkeypatch.setattr(L, "interpret_mode", lambda: not chip)
    x = jnp.zeros((1, seq_len, 8), jnp.bfloat16)
    assert L._flash_ok(x, window, kv) is takes


@pytest.mark.parametrize("softcap,window", [
    (0.0, 0), (5.0, 5), (0.0, jnp.int32(7))],
    ids=["plain", "softcap_window", "traced_window"])
def test_cached_attention_matches_attend_over_written_cache(softcap, window):
    """Attention over a head-major cache plus the row's new entry equals
    ``attend`` over the cache with the entry written into its slot."""
    B, K, G, T, hd = 3, 2, 2, 12, 16
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (B, 1, K * G, hd))
    k = jax.random.normal(ks[1], (B, K, T, hd))
    v = jax.random.normal(ks[2], (B, K, T, hd))
    k_new = jax.random.normal(ks[3], (B, K, hd))
    v_new = jax.random.normal(ks[4], (B, K, hd))
    s = np.arange(T)
    slot_pos = jnp.asarray(np.stack([
        np.where(s < 7, s, -1),                 # filling: slot 7 is empty
        np.where(s + T < 20, s + T, s),         # ring: slot 8 holds 8, evicted
        np.where(np.isin(s, [2, 5, 6]) | (s > 5), -1, s),   # masked slots
    ]), jnp.int32)
    q_pos = jnp.asarray([7, 20, 5], jnp.int32)
    slot = q_pos % T
    visible = (slot_pos >= 0) & (jnp.arange(T) != slot[:, None])
    got = L.attend_cached(q, k, v, k_new, v_new, q_pos, slot_pos,
                          window=window, softcap=softcap, k_valid=visible)

    b = jnp.arange(B)
    kw = k.at[b, :, slot].set(k_new).transpose(0, 2, 1, 3)
    vw = v.at[b, :, slot].set(v_new).transpose(0, 2, 1, 3)
    pos = slot_pos.at[b, slot].set(q_pos)
    want = L.attend(q, kw, vw, q_pos[:, None], pos, causal=True,
                    window=window, softcap=softcap, k_valid=pos >= 0)
    assert got.shape == want.shape == (B, 1, K * G, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
