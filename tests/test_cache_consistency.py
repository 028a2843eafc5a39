"""Prefill + cached decode must give the logits of one full forward pass.

Each arch runs at its ``reduced()`` size in float32.  The two paths do the
same arithmetic in a different order (a blocked prefill and one-token
steps against the cache, versus one pass over the whole sequence), so
they may differ only by float32 rounding.  A cache bug (a wrong slot, a
wrong position, a stale recurrent state) moves the logits by O(1).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, reduced
from repro.serving.engine import cached_and_full_logits, serving_config

# Logits are of magnitude ~1.  In float32 the two paths differ by about
# 5e-6 here (granite's blocked attention, rwkv6's chunked WKV scan against
# its one-step recurrence); 1e-4 leaves twenty times that.
ATOL = 1e-4


@pytest.mark.parametrize("arch", ["granite-3-2b", "rwkv6-7b", "gemma2-2b",
                                  "recurrentgemma-9b"])
def test_cached_decode_matches_forward(arch):
    cfg = serving_config(reduced(get_config(arch)))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    # both lengths are whole WKV chunks (32), which rwkv6's scan needs
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    cached, full = cached_and_full_logits(model, params, tokens,
                                          prompt_len=32, max_len=64)
    assert cached.shape == full.shape == (2, 33, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               atol=ATOL, rtol=0)
