"""The Pallas flash-attention path must agree with the XLA path at the
model level (full forward of a dense and a local-window arch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model, reduced
from repro.models import layers as L


@pytest.mark.parametrize("name", ["granite-3-2b", "gemma2-2b"])
def test_flash_path_matches_xla(name):
    cfg = reduced(ARCHS[name])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    try:
        L.set_attention_impl("xla")
        ref, _ = model.forward(params, tokens)
        L.set_attention_impl("pallas")
        out, _ = model.forward(params, tokens)
    finally:
        L.set_attention_impl("xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)


def test_ragged_masks_fall_back_to_xla():
    """prefill (k_valid mask) must not take the kernel path."""
    try:
        L.set_attention_impl("pallas")
        assert not L._flash_ok(None, 0, 0.0, jnp.ones((2, 8), bool))
        assert L._flash_ok(None, 0, 0.0, None)
        # traced per-layer window scalars are not static ints -> fallback
        assert not L._flash_ok(None, jnp.int32(4), 0.0, None)
    finally:
        L.set_attention_impl("xla")
    assert not L._flash_ok(None, 0, 0.0, None)   # toggle off -> xla


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
