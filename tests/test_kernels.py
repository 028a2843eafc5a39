"""Pallas-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.wkv6 import wkv6

KEY = jax.random.key(0)


def rand(k, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, k), shape) * scale
            ).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,K,S,d,lengths", [
    (1, 4, 4, 128, 64, None), (2, 8, 4, 256, 64, None),
    (1, 8, 2, 256, 128, None), (2, 4, 1, 128, 256, None),
    # right-padded rows, groups of 4: one at full length, one that ends
    # mid-block, one whose last query blocks are all padding
    (3, 8, 2, 1024, 64, (1024, 700, 200)),
    (3, 8, 2, 1024, 128, (1024, 300, 130)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("opts", [
    dict(causal=True), dict(causal=True, window=64),
    dict(causal=True, softcap=30.0), dict(causal=False),
])
def test_flash_attention(B, H, K, S, d, lengths, dtype, opts):
    q = rand(1, (B, H, S, d), dtype)
    k = rand(2, (B, K, S, d), dtype)
    v = rand(3, (B, K, S, d), dtype)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    out = np.asarray(flash_attention(q, k, v, lengths, interpret=True,
                                     **opts), np.float32)
    want = np.asarray(ref.mha_reference(q, k, v, lengths=lengths, **opts),
                      np.float32)
    assert np.isfinite(out).all()
    live = np.arange(S) < (S if lengths is None
                           else np.asarray(lengths)[:, None, None])
    live = np.broadcast_to(live, (B, H, S))
    np.testing.assert_allclose(out[live], want[live],
                               atol=TOL[dtype], rtol=TOL[dtype] * 10)


def test_flash_attention_gradient_is_the_references():
    """The served entry differentiates through the plain reference."""
    from repro.kernels import ops
    q, k, v = rand(1, (2, 4, 128, 32)), rand(2, (2, 2, 128, 32)), rand(
        3, (2, 2, 128, 32))
    lengths = jnp.asarray([128, 50], jnp.int32)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    got = jax.grad(loss(lambda *a: ops.flash_attention(*a, lengths)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: ref.mha_reference(*a, lengths=lengths)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,H,K,T,d", [
    (2, 8, 2, 1024, 64), (1, 4, 4, 512, 128), (3, 16, 4, 2048, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, K, T, d, dtype):
    q = rand(1, (B, H, d), dtype)
    k = rand(2, (B, K, T, d), dtype)
    v = rand(3, (B, K, T, d), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, T + 1, size=B), jnp.int32)
    out = decode_attention(q, k, v, lengths, interpret=True)
    want = ref.decode_attention_reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype] * 10)


@pytest.mark.parametrize("B,S,H,N,chunk", [
    (2, 128, 4, 64, 32), (1, 64, 2, 32, 16), (2, 96, 4, 64, 32),
])
def test_wkv6_kernel(B, S, H, N, chunk):
    r = rand(4, (B, S, H, N), scale=0.5)
    k = rand(5, (B, S, H, N), scale=0.5)
    v = rand(6, (B, S, H, N))
    logw = -jnp.exp(rand(7, (B, S, H, N), scale=0.5))
    u = rand(8, (H, N), scale=0.1)
    s0 = rand(9, (B, H, N, N), scale=0.1)
    out, state = wkv6(r, k, v, logw, u, s0, chunk=chunk, interpret=True)
    want_o, want_s = ref.wkv6_reference(r, k, v, logw, u, s0)
    np.testing.assert_allclose(out, want_o, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(state, want_s, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("B,S,R,chunk,block_r", [
    (2, 256, 512, 128, 512), (1, 128, 256, 64, 128), (3, 64, 1024, 64, 256),
])
def test_rglru_kernel(B, S, R, chunk, block_r):
    a = jax.random.uniform(jax.random.fold_in(KEY, 10), (B, S, R),
                           minval=0.8, maxval=0.999)
    b = rand(11, (B, S, R), scale=0.1)
    s0 = rand(12, (B, R))
    seq, last = rglru_scan(a, b, s0, chunk=chunk, block_r=block_r,
                           interpret=True)
    want_seq, want_last = ref.rglru_reference(a, b, s0)
    np.testing.assert_allclose(seq, want_seq, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(last, want_last, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("M,K,N", [(128, 512, 128), (256, 1024, 256),
                                   (128, 2048, 384)])
def test_int8_matmul(M, K, N):
    x = rand(13, (M, K))
    w = rand(14, (K, N))
    xq, sx = ref.quantize_rowwise(x)
    wq_t, sw = ref.quantize_rowwise(w.T)
    wq = wq_t.T
    out = int8_matmul(xq, wq, sx, sw, interpret=True)
    want = ref.int8_matmul_reference(xq, wq, sx, sw)
    np.testing.assert_allclose(out, want, atol=1e-3, rtol=1e-4)
    # quantized result close to the fp32 matmul (end-to-end sanity)
    rel = np.linalg.norm(out - x @ w) / np.linalg.norm(x @ w)
    assert rel < 0.05


def test_wkv_chunked_model_path_matches_kernel():
    """The model's associative-scan WKV == the Pallas chunk kernel."""
    from repro.models.rwkv6 import wkv_chunked
    B, S, H, N = 2, 128, 4, 32
    r = rand(20, (B, S, H, N), scale=0.5)
    k = rand(21, (B, S, H, N), scale=0.5)
    v = rand(22, (B, S, H, N))
    logw = -jnp.exp(rand(23, (B, S, H, N), scale=0.5))
    u = rand(24, (H, N), scale=0.1)
    s0 = rand(25, (B, H, N, N), scale=0.1)
    o1, s1 = wkv_chunked(r, k, v, logw, u, s0)
    o2, s2 = wkv6(r, k, v, logw, u, s0, interpret=True)
    np.testing.assert_allclose(o1, o2, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(s1, s2, atol=2e-4, rtol=1e-3)
