"""Property tests for the blocked-ELL SpMM kernel and its oracle.

The kernel/oracle/exchange triangle: :func:`repro.kernels.spmv_ell.spmm_ell`
must match the jnp oracle for random shapes/dtypes/ELL widths, and its k=1
column must degenerate *exactly* (bitwise) to the existing SpMV kernel --
that exactness is what makes the batched serving path a drop-in replacement
for the per-column loop.
"""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.spmv_ell import spmm_ell, spmv_ell

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "R,K,N,C",
    [
        (8, 3, 32, 1),  # degenerate single column
        (300, 17, 1000, 5),  # ragged everything
        (256, 128, 128, 64),  # K at the lane width, wide rhs
        (513, 1, 7, 2),  # single-entry rows
        (70, 200, 64, 130),  # K and C both above one tile
    ],
)
def test_spmm_ell_shapes(R, K, N, C, dtype):
    rng = np.random.default_rng(R * 1000 + K)  # order-independent draws
    data = rng.normal(size=(R, K)).astype(np.float32)
    cols = rng.integers(0, N, size=(R, K)).astype(np.int32)
    x = rng.normal(size=(N, C)).astype(np.float32)
    d, xx = jnp.asarray(data, dtype), jnp.asarray(x, dtype)
    out = spmm_ell(d, jnp.asarray(cols), xx, interpret=True)
    want = ref.spmm_ell(d, jnp.asarray(cols), xx)
    assert out.shape == (R, C)
    # bf16 tolerance covers a K-term bf16 accumulation whose reduction order
    # may differ between the jitted kernel and the eager oracle
    tol = 2e-5 if dtype == np.float32 else 2e-2 * max(np.sqrt(K), 1.0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.slow
@given(
    r=st.integers(1, 64),
    k=st.integers(1, 16),
    n=st.integers(1, 128),
    c=st.integers(1, 8),
    seed=st.integers(0, 99),
)
@settings(max_examples=15, deadline=None)
def test_spmm_ell_property(r, k, n, c, seed):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(r, k)).astype(np.float32)
    cols = rng.integers(0, n, size=(r, k)).astype(np.int32)
    x = rng.normal(size=(n, c)).astype(np.float32)
    out = spmm_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x), interpret=True)
    want = ref.spmm_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@given(
    r=st.integers(1, 80),
    k=st.integers(1, 20),
    n=st.integers(1, 96),
    seed=st.integers(0, 99),
)
@settings(max_examples=15, deadline=None)
def test_spmm_k1_degenerates_to_spmv_exactly(r, k, n, seed):
    """Interpreted, a single-column rhs reproduces the SpMV kernel bit for
    bit (one degenerate column tile, the same reduce over K).  The match is
    pinned in interpret mode only: compiled, the two kernels reduce along
    different axes and may differ in the last bit."""
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(r, k)).astype(np.float32)
    cols = rng.integers(0, n, size=(r, k)).astype(np.int32)
    v = rng.normal(size=(n,)).astype(np.float32)
    mv = spmv_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(v), interpret=True)
    mm = spmm_ell(
        jnp.asarray(data), jnp.asarray(cols), jnp.asarray(v[:, None]), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(mm)[:, 0], np.asarray(mv))


@given(seed=st.integers(0, 99), c=st.integers(2, 6))
@settings(max_examples=10, deadline=None)
def test_spmm_oracle_columns_are_spmv_oracles(seed, c):
    """The oracle itself is column-separable: column c of spmm == spmv on
    column c (locks the reduction-order contract the kernel relies on)."""
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(40, 7)).astype(np.float32)
    cols = rng.integers(0, 50, size=(40, 7)).astype(np.int32)
    x = rng.normal(size=(50, c)).astype(np.float32)
    mm = np.asarray(ref.spmm_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x)))
    for j in range(c):
        mv = np.asarray(
            ref.spmv_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x[:, j]))
        )
        np.testing.assert_array_equal(mm[:, j], mv)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_spmm_ell_wide_sweep(dtype):
    """Interpret-mode Pallas sweep across tile boundaries (slow marker)."""
    for R, K, N, C in [(64, 96, 256, 64), (129, 64, 300, 129), (256, 130, 64, 16)]:
        rng = np.random.default_rng(R * 1000 + K)
        data = rng.normal(size=(R, K)).astype(np.float32)
        cols = rng.integers(0, N, size=(R, K)).astype(np.int32)
        x = rng.normal(size=(N, C)).astype(np.float32)
        d, xx = jnp.asarray(data, dtype), jnp.asarray(x, dtype)
        out = spmm_ell(d, jnp.asarray(cols), xx, interpret=True)
        want = ref.spmm_ell(d, jnp.asarray(cols), xx)
        tol = 2e-5 if dtype == np.float32 else 2e-2 * max(np.sqrt(K), 1.0)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )
