"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import unroll
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.graph_filter import graph_filter, graph_filter_ref
from repro.kernels.graph_filter.ops import pallas_profitable, pick_block_d
from repro.kernels.ssm_scan import wkv, wkv_ref

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 5e-2}


def _gf_inputs(n, d, K, dtype=jnp.float32):
    key = jax.random.PRNGKey(n + d + K)
    S = jax.random.uniform(key, (n, n))
    S = (S / S.sum(1, keepdims=True)).astype(dtype)
    W = (jax.random.normal(jax.random.PRNGKey(1), (n, d))).astype(dtype)
    h = (jax.random.normal(jax.random.PRNGKey(2), (K + 1,)) * 0.5
         ).astype(dtype)
    return S, W, h


# ------------------------------------------------------------ graph filter
# shapes deliberately include non-aligned n (not ×8) and d (not ×128):
# the pad→kernel→slice contract must be exact, not just tile-friendly.
GF_SHAPES = [(8, 16, 1), (100, 650, 2), (64, 128, 4), (33, 100, 2),
             (9, 5, 1)]


@pytest.mark.parametrize("n,d,K", GF_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_filter_sweep(n, d, K, dtype):
    S, W, h = _gf_inputs(n, d, K, dtype)
    y = graph_filter(S, W, h, impl="pallas")
    yr = graph_filter_ref(S, W, h)
    yu = unroll.graph_filter(S, W, h)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yu, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n,d,K", [(8, 16, 1), (33, 100, 2), (64, 128, 4)])
def test_graph_filter_vjp_parity(n, d, K):
    """Custom VJP vs autodiff-through-ref AND autodiff-through-unroll for
    ALL THREE cotangents (dS, dW, dh) — the meta-gradient path of
    ``mix="pallas"`` must not silently stop any gradient."""
    S, W, h = _gf_inputs(n, d, K)

    def loss(fn):
        return lambda S, W, h: jnp.sum(fn(S, W, h) ** 2)

    g = jax.grad(loss(lambda S, W, h: graph_filter(S, W, h, impl="pallas")),
                 argnums=(0, 1, 2))(S, W, h)
    gr = jax.grad(loss(graph_filter_ref), argnums=(0, 1, 2))(S, W, h)
    gu = jax.grad(loss(unroll.graph_filter), argnums=(0, 1, 2))(S, W, h)
    for got, want_r, want_u, name in zip(g, gr, gu, ("dS", "dW", "dh")):
        np.testing.assert_allclose(got, want_r, atol=5e-4, rtol=5e-4,
                                   err_msg=f"{name} vs ref")
        np.testing.assert_allclose(got, want_u, atol=5e-4, rtol=5e-4,
                                   err_msg=f"{name} vs unroll")


def test_graph_filter_auto_dispatch():
    """impl='auto' falls back to the jitted ref for unprofitable shapes
    (bit-exact with it) and stays parity-close on kernel-worthy ones."""
    S, W, h = _gf_inputs(4, 6, 1)            # tiny: pad waste > 4x
    assert not pallas_profitable(4, 6)
    y = graph_filter(S, W, h, impl="auto")
    yr = jax.jit(graph_filter_ref)(S, W, h)
    assert np.array_equal(np.asarray(y), np.asarray(yr))
    S, W, h = _gf_inputs(100, 650, 2)        # profitable: kernel path
    assert pallas_profitable(100, 650)
    np.testing.assert_allclose(graph_filter(S, W, h, impl="auto"),
                               graph_filter_ref(S, W, h), atol=5e-5,
                               rtol=5e-5)
    with pytest.raises(ValueError, match="impl must be one of"):
        graph_filter(S, W, h, impl="horner")


def test_graph_filter_block_d_invariance():
    """Same result for any valid column block size (and the auto pick)."""
    S, W, h = _gf_inputs(33, 300, 2)
    y_auto = graph_filter(S, W, h, impl="pallas")
    y_128 = graph_filter(S, W, h, impl="pallas", block_d=128)
    assert pick_block_d(33, 300) in (128, 256)
    np.testing.assert_allclose(y_auto, y_128, atol=1e-6)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,H,KV,S,dh,win", [
    (1, 4, 4, 64, 32, 0),       # MHA global
    (2, 4, 2, 80, 32, 0),       # GQA + seq padding
    (1, 8, 2, 128, 64, 16),     # GQA + sliding window
    (1, 2, 1, 48, 16, 8),       # tiny dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, dh, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + dh), 3)
    q = jax.random.normal(ks[0], (B, H, S, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, S, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, S, dh)).astype(dtype)
    o = flash_attention(q, k, v, causal=True, window=win,
                        block_q=32, block_kv=32)
    orf = attention_ref(q, k, v, causal=True, window=win)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32),
                               atol=10 * TOL[dtype], rtol=10 * TOL[dtype])


def test_flash_attention_block_shape_invariance():
    B, H, S, dh = 1, 2, 96, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, H, S, dh))
    v = jax.random.normal(ks[2], (B, H, S, dh))
    o1 = flash_attention(q, k, v, block_q=16, block_kv=48)
    o2 = flash_attention(q, k, v, block_q=96, block_kv=96)
    np.testing.assert_allclose(o1, o2, atol=1e-5)


# ----------------------------------------------------------------- wkv
@pytest.mark.parametrize("B,H,T,dk,chunk", [
    (1, 2, 32, 16, 8), (2, 3, 50, 16, 16), (1, 4, 64, 64, 64),
    (2, 1, 17, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv_sweep(B, H, T, dk, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(T + dk), 5)
    mk = lambda i: (0.5 * jax.random.normal(ks[i], (B, H, T, dk))).astype(dtype)
    r, k, v = mk(0), mk(1), mk(2)
    w = (jax.nn.sigmoid(mk(3).astype(jnp.float32)) * 0.5 + 0.5).astype(dtype)
    u = (0.1 * jax.random.normal(ks[4], (H, dk))).astype(dtype)
    y, Sf = wkv(r, k, v, w, u, chunk=chunk)
    yr, Sr = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=20 * TOL[dtype], rtol=20 * TOL[dtype])
    np.testing.assert_allclose(np.asarray(Sf), np.asarray(Sr),
                               atol=20 * TOL[dtype], rtol=20 * TOL[dtype])


def test_wkv_state_resumes():
    """Final kernel state == ref state => serving can resume the recurrence."""
    B, H, T, dk = 1, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    mk = lambda i: 0.5 * jax.random.normal(ks[i], (B, H, T, dk))
    r, k, v = mk(0), mk(1), mk(2)
    w = jax.nn.sigmoid(mk(3)) * 0.5 + 0.5
    u = 0.1 * jax.random.normal(ks[4], (H, dk))
    _, S_half = wkv(r[:, :, :12], k[:, :, :12], v[:, :, :12], w[:, :, :12],
                    u, chunk=4)
    y2, S_full = wkv_ref(r[:, :, 12:], k[:, :, 12:], v[:, :, 12:],
                         w[:, :, 12:], u, S0=S_half)
    _, S_direct = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(S_full), np.asarray(S_direct),
                               atol=1e-5)
