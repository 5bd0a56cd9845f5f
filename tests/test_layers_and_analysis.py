"""Unit tests: core layers (RoPE, norms, GQA grouping) and the HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.launch.hlo_analysis import analyze
from repro.models.layers import attend_chunked, attend_dot, rmsnorm, rmsnorm_params, rope
from repro.models.sharding import init_params

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def test_rope_preserves_norm():
    x = jnp.asarray(RNG.normal(size=(2, 8, 4, 16)), jnp.float32)
    pos = jnp.arange(8)[None, :]
    y = rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )


def test_rope_relative_position_property():
    """q_i . k_j depends only on i - j after rotation."""
    D = 16
    q = jnp.asarray(RNG.normal(size=(1, 1, 1, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1, 1, D)), jnp.float32)

    def dot_at(i, j):
        qi = rope(q, jnp.asarray([[i]]))
        kj = rope(k, jnp.asarray([[j]]))
        return float((qi * kj).sum())

    assert dot_at(5, 3) == pytest.approx(dot_at(9, 7), rel=1e-4)
    assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), rel=1e-3)


def test_partial_rope_leaves_tail_untouched():
    x = jnp.asarray(RNG.normal(size=(1, 4, 2, 16)), jnp.float32)
    y = rope(x, jnp.arange(4)[None], fraction=0.5)
    np.testing.assert_array_equal(np.asarray(x[..., 8:]), np.asarray(y[..., 8:]))
    assert not np.allclose(np.asarray(x[..., :8]), np.asarray(y[..., :8]))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


@given(scale=st.floats(0.5, 10.0), seed=st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_rmsnorm_scale_invariance(scale, seed):
    """Scale invariance is exact up to the eps regularizer (x kept O(1))."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 3, 8)), jnp.float32)
    p = init_params(rmsnorm_params(8), jax.random.PRNGKey(0), jnp.float32)
    a = rmsnorm(p, x)
    b = rmsnorm(p, x * scale)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=5e-3)


# ---------------------------------------------------------------------------
# attention equivalences
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("win", [None, 8])
def test_chunked_equals_dot_attention(win):
    q = jnp.asarray(RNG.normal(size=(2, 24, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 24, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 24, 2, 16)), jnp.float32)
    a = attend_dot(q, k, v, causal=True, window=win)
    b = attend_chunked(q, k, v, causal=True, window=win, block=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# HLO analyzer on a known program
# ---------------------------------------------------------------------------


def test_analyzer_counts_scanned_dot_flops_and_trips():
    D, L = 64, 7

    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None

        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    txt = jax.jit(f).lower(jax.ShapeDtypeStruct((D, D), jnp.float32)).compile().as_text()
    st_ = analyze(txt)
    # one D^3 matmul per trip: 2*D^3*L FLOPs
    assert st_.flops == pytest.approx(2 * D**3 * L, rel=1e-6)
    assert st_.collective_bytes == 0.0
    # memory: at least the L carry writes of the [D,D] f32 tensor
    assert st_.mem_bytes >= L * D * D * 4


def test_analyzer_handles_empty_program():
    st_ = analyze("")
    assert st_.flops == 0 and st_.collective_bytes == 0
