"""Compressor dtype/round-trip regression tests.

The int8 quantizer must round-trip a payload in the payload's own floating
dtype: a bfloat16 leaf that comes back float32 silently upcasts the
error-feedback residual state carried across steps (the PR-4 bugfix).  These
run in-process under a 1-device shard_map so ``pmax`` has its axis in scope.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm.compression import Compressor


def _round_trip(x: jnp.ndarray):
    """compress -> (trivial 1-pod psum) -> decompress, plus the residual."""
    comp = Compressor()
    mesh = jax.make_mesh((1,), ("pod",))

    def body(v):
        q, scale = comp.compress(v[0], "pod")
        q_sum = jax.lax.psum(q.astype(jnp.int32), "pod")
        out = comp.decompress(q_sum, scale)
        residual = v[0] - comp.decompress(q.astype(jnp.int32), scale)
        return out[None], residual[None]

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=(P("pod"), P("pod")))
    )
    out, res = fn(x[None])
    return out[0], res[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_round_trip_preserves_dtype(dtype):
    x = jnp.asarray(np.linspace(-1.0, 1.0, 32), dtype)
    out, res = _round_trip(x)
    assert out.dtype == dtype, f"payload upcast: {dtype} -> {out.dtype}"
    assert res.dtype == dtype, f"residual upcast: {dtype} -> {res.dtype}"


def test_round_trip_reconstructs_float32():
    x = jnp.asarray(np.linspace(-3.0, 3.0, 64), jnp.float32)
    out, res = _round_trip(x)
    # |error| <= scale/2 per element; with amax=3 and qmax=127 that is ~0.012
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=3.0 / 127)
    # error feedback closes the loop: x == decompressed + residual
    np.testing.assert_allclose(
        np.asarray(out + res), np.asarray(x), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_all_zero_payload_is_finite(dtype):
    """An all-zero shard must keep a positive scale in the payload's own
    dtype (float16 is the sharp case: float32.tiny flushes to zero there,
    and a float32 constant would promote the scale out of the dtype)."""
    out, res = _round_trip(jnp.zeros((16,), dtype))
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(out, np.float32), 0.0)
    np.testing.assert_array_equal(np.asarray(res, np.float32), 0.0)


def test_nonfinite_element_never_poisons_neighbors():
    """One inf/nan in the shard must not set the quantization scale (an inf
    scale decodes EVERY element to nan): finite neighbors keep the normal
    error bound, inf saturates sign-preserved at the finite amax, nan
    contributes 0 -- and the error-feedback residual keeps the
    non-finiteness at exactly those elements so divergence is not lost."""
    x = np.linspace(-3.0, 3.0, 32).astype(np.float32)
    x[4], x[9], x[20] = np.inf, -np.inf, np.nan
    out, res = _round_trip(jnp.asarray(x))
    out, res = np.asarray(out), np.asarray(res)
    finite = np.isfinite(x)
    assert np.isfinite(out).all()  # summed codes cannot carry non-finite
    np.testing.assert_allclose(out[finite], x[finite], atol=3.0 / 127)
    assert out[4] > 0 and out[9] < 0 and out[4] == -out[9] == np.abs(out[finite]).max()
    assert out[20] == 0.0
    assert np.isposinf(res[4]) and np.isneginf(res[9]) and np.isnan(res[20])
    np.testing.assert_allclose(out[finite] + res[finite], x[finite], rtol=1e-6, atol=1e-6)


def test_decompress_multiplies_at_full_precision():
    """Multi-pod int32 sums exceed bf16's exact-integer range (256); the
    dequantize multiply must run at float32-or-wider and round only the
    final product to the payload dtype."""
    comp = Compressor()
    q_sum = jnp.asarray([514], jnp.int32)  # rounds to 512 if cast to bf16
    scale = jnp.asarray(3.0, jnp.bfloat16)
    out = comp.decompress(q_sum, scale)
    assert out.dtype == jnp.bfloat16
    # 514 * 3 = 1542 -> 1544 in bf16; a bf16-cast q_sum would give
    # 512 * 3 = 1536
    assert float(out[0]) == 1544.0


def test_compress_scale_dtype_follows_payload():
    comp = Compressor()
    mesh = jax.make_mesh((1,), ("pod",))

    def body(v):
        q, scale = comp.compress(v[0], "pod")
        return q[None], scale[None]

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=(P("pod"), P("pod")))
    )
    for dtype in (jnp.float32, jnp.bfloat16, jnp.float16):
        q, scale = fn(jnp.ones((1, 8), dtype))
        assert q.dtype == jnp.int8
        assert scale.dtype == dtype
