"""Per-kernel correctness sweeps: Pallas (interpret) vs pure-jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.spmv_ell import spmv_ell
from repro.kernels.ssd_scan import ssd_scan_kernel
from repro.models.ssd import ssd_chunked

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# spmv_ell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("R,K,N", [(8, 3, 32), (300, 17, 1000), (256, 128, 128), (513, 1, 7)])
def test_spmv_ell_shapes(R, K, N, dtype):
    data = RNG.normal(size=(R, K)).astype(np.float32)
    cols = RNG.integers(0, N, size=(R, K)).astype(np.int32)
    x = RNG.normal(size=(N,)).astype(np.float32)
    d, xx = jnp.asarray(data, dtype), jnp.asarray(x, dtype)
    out = spmv_ell(d, jnp.asarray(cols), xx, interpret=True)
    want = ref.spmv_ell(d, jnp.asarray(cols), xx)
    tol = 2e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.slow
@given(
    r=st.integers(1, 64),
    k=st.integers(1, 16),
    n=st.integers(1, 128),
    seed=st.integers(0, 99),
)
@settings(max_examples=15, deadline=None)
def test_spmv_ell_property(r, k, n, seed):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(r, k)).astype(np.float32)
    cols = rng.integers(0, n, size=(r, k)).astype(np.int32)
    x = rng.normal(size=(n,)).astype(np.float32)
    out = spmv_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x), interpret=True)
    want = ref.spmv_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,KV,D,causal,win",
    [
        (2, 64, 64, 4, 2, 32, True, None),
        (1, 48, 48, 4, 4, 16, True, 16),
        (2, 16, 64, 4, 2, 32, True, None),  # cached decode-style Sq < Sk
        (1, 64, 64, 2, 1, 64, False, None),  # bidirectional (encoder)
        (1, 100, 100, 2, 2, 32, True, 32),  # non-multiple of block
    ],
)
def test_flash_attention(B, Sq, Sk, H, KV, D, causal, win, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=win,
                                 block_q=32, block_k=32, interpret=True)
    want = np.stack(
        [np.asarray(ref.attention(q[b], k[b], v[b], causal=causal, window=win), np.float32)
         for b in range(B)]
    )
    tol = 2e-4 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,P,N,Q",
    [(2, 32, 3, 4, 8, 8), (1, 50, 2, 16, 8, 16), (2, 128, 4, 8, 16, 32), (1, 7, 1, 2, 3, 4)],
)
def test_ssd_scan_vs_oracles(B, S, H, P, N, Q):
    x = RNG.normal(size=(B, S, H, P)).astype(np.float32)
    loga = (-np.abs(RNG.normal(size=(B, S, H))) * 0.2).astype(np.float32)
    b = RNG.normal(size=(B, S, N)).astype(np.float32)
    c = RNG.normal(size=(B, S, N)).astype(np.float32)
    out = ssd_scan_kernel(jnp.asarray(x), jnp.asarray(loga), jnp.asarray(b),
                          jnp.asarray(c), chunk=Q, interpret=True)
    chunked = ssd_chunked(jnp.asarray(x), jnp.asarray(loga), jnp.asarray(b),
                          jnp.asarray(c), chunk=Q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(chunked), rtol=2e-4, atol=2e-4)
    for bi in range(B):
        seq = ref.ssd_scan(jnp.asarray(x[bi]), jnp.exp(jnp.asarray(loga[bi])),
                           jnp.asarray(b[bi]), jnp.asarray(c[bi]))
        np.testing.assert_allclose(np.asarray(out[bi]), np.asarray(seq), rtol=5e-4, atol=5e-4)


@given(seed=st.integers(0, 99), q=st.sampled_from([4, 8, 16]))
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_invariance(seed, q):
    """Output must not depend on the chunk size (pure blocking parameter)."""
    rng = np.random.default_rng(seed)
    B, S, H, P, N = 1, 24, 2, 4, 6
    x = rng.normal(size=(B, S, H, P)).astype(np.float32)
    loga = (-np.abs(rng.normal(size=(B, S, H))) * 0.3).astype(np.float32)
    b = rng.normal(size=(B, S, N)).astype(np.float32)
    c = rng.normal(size=(B, S, N)).astype(np.float32)
    outs = [
        np.asarray(ssd_scan_kernel(jnp.asarray(x), jnp.asarray(loga), jnp.asarray(b),
                                   jnp.asarray(c), chunk=qq, interpret=True))
        for qq in (q, S)
    ]
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)
