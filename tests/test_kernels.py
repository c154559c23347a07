"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
all in interpret mode (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention_bh
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_ref
from repro.kernels.sinkhorn.ops import sinkhorn_iteration
from repro.kernels.sinkhorn.ref import sinkhorn_iteration_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_naive, ssd_ref


@pytest.mark.parametrize("BH,S,D,causal,window,bq,bk,dtype", [
    (4, 256, 64, True, 0, 128, 128, jnp.float32),
    (2, 512, 128, True, 0, 256, 128, jnp.float32),
    (2, 256, 64, False, 0, 128, 64, jnp.float32),
    (2, 512, 64, True, 100, 128, 128, jnp.float32),
    (2, 256, 128, True, 0, 128, 128, jnp.bfloat16),
    (1, 128, 256, True, 64, 64, 64, jnp.float32),
])
def test_flash_attention_sweep(BH, S, D, causal, window, bq, bk, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    out = flash_attention_bh(q, k, v, causal=causal, window=window,
                             bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("G", [1, 2, 4])
def test_flash_attention_gqa(G):
    rng = np.random.default_rng(1)
    B, S, Kh, D = 2, 256, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, Kh, G, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Kh, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Kh, D)), jnp.float32)
    out = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    from repro.models.attention import blocked_attention
    ref = blocked_attention(q, k, v, jnp.arange(S), jnp.arange(S),
                            kind="causal", block_kv=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-5)


@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (64, 4, 16, 2, 8, 16),
    (128, 2, 32, 1, 16, 32),
    (64, 8, 64, 8, 8, 64),
])
def test_ssd_scan_sweep(S, H, P, G, N, chunk):
    rng = np.random.default_rng(2)
    b = 2
    x = jnp.asarray(rng.standard_normal((b, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.random((b, S, H)) * 0.5 + 0.1, jnp.float32)
    A = jnp.asarray(-rng.random(H) - 0.2, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.float32)
    yk, sk = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yn, sn = ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sn), atol=2e-3)


def test_ssd_chunked_model_path_matches_naive():
    """models/ssm.ssd_chunked (the train path) vs sequential recurrence."""
    rng = np.random.default_rng(3)
    b, S, H, P, G, N = 1, 48, 2, 8, 1, 4
    x = jnp.asarray(rng.standard_normal((b, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.random((b, S, H)) + 0.05, jnp.float32)
    A = jnp.asarray(-rng.random(H) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.float32)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm, chunk=16)
    yn, sn = ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yn), atol=2e-3)


@pytest.mark.parametrize("S,W,chunk", [(64, 32, 16), (128, 128, 64),
                                       (32, 256, 32),
                                       (200, 16, 128),   # end padding
                                       (50, 16, 12)])    # chunk → 16, pad
def test_rglru_scan_sweep(S, W, chunk):
    rng = np.random.default_rng(4)
    B = 2
    a = jnp.asarray(rng.random((B, S, W)) * 0.9, jnp.float32)
    bx = jnp.asarray(rng.standard_normal((B, S, W)), jnp.float32)
    yk = rglru_scan(a, bx, chunk=chunk, interpret=True)
    yr = rglru_ref(a, bx)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-4)


@pytest.mark.parametrize("B,S,W", [
    (15, 48, 16),     # the learned forecaster's shape: batch = stacked
                      # signal×region columns, window-length sequences
    (5, 29, 16),      # batch = regions, odd non-padded length
    (2, 7, 15),       # short odd sequence, odd (non-lane-aligned) width
])
def test_rglru_scan_forecast_shapes(B, S, W):
    """Forecast-shaped inputs through the kernel entry (default chunk, so
    odd lengths hit the L=S single-chunk path with no padding) — pins the
    learned forecaster's pallas inference path independently of the model
    tests."""
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.random((B, S, W)) * 0.95, jnp.float32)
    bx = jnp.asarray(rng.standard_normal((B, S, W)), jnp.float32)
    yk = rglru_scan(a, bx, interpret=True)
    yr = rglru_ref(a, bx)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-4)


def test_rglru_model_assoc_scan_matches_naive():
    """models/rglru associative scan == sequential recurrence."""
    import repro.models.rglru as rg
    rng = np.random.default_rng(5)
    B, S, W = 2, 32, 16
    x = jnp.asarray(rng.standard_normal((B, S, W)), jnp.float32)
    p, _ = __import__("repro.models.common", fromlist=["split_tree"]) \
        .split_tree(rg.block_init(jax.random.PRNGKey(0), W, lru_width=W))
    a, bx = rg._gates(x, p)
    y, _ = rg.rglru_scan(x, p)
    yn = rglru_ref(a, bx)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yn),
                               atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), m_blocks=st.integers(1, 4),
       n=st.integers(2, 9))
def test_sinkhorn_kernel_property(seed, m_blocks, n):
    """Fused kernel == reference iteration for random instances; the g
    update keeps the column marginals consistent."""
    rng = np.random.default_rng(seed)
    M = 128 * m_blocks
    C = jnp.asarray(rng.random((M, n)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    log_a = jnp.full((M,), -np.log(M), jnp.float32)
    b = rng.random(n) + 0.5
    log_b = jnp.asarray(np.log(b / b.sum()), jnp.float32)
    eps = float(rng.choice([0.05, 0.2, 1.0]))
    f_k, g_k = sinkhorn_iteration(C, None, g, log_a, log_b, eps,
                                  interpret=True)
    f_r, g_r = sinkhorn_iteration_ref(C, None, g, log_a, log_b, eps)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r), atol=2e-4)
    # after the g update, column marginals of the implied plan match b
    X = np.exp((np.asarray(f_k)[:, None] + np.asarray(g_k)[None, :]
                - np.asarray(C)) / eps)
    np.testing.assert_allclose(X.sum(0), b / b.sum(), rtol=5e-3)
