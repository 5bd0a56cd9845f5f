"""Kernel micro-benchmarks: Pallas kernels vs jnp oracle wall-times.

The kernels run in the mode :mod:`repro.kernels.ops` picks for the backend
(compiled on a TPU, interpreted elsewhere) and each row names that mode.
Interpret-mode timing is a correctness-path sanity check, not TPU
performance.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.spmv_ell import spmm_ell, spmv_ell
from repro.kernels.ssd_scan import ssd_scan_kernel
from repro.models.ssd import ssd_chunked

RNG = np.random.default_rng(0)


def main(smoke: bool = False) -> None:
    print("name,us_per_call,derived")
    interpret = ops._interpret()
    mode = "interpret" if interpret else "mosaic"
    iters = 3 if smoke else 10
    # spmv
    R, N = (128, 512) if smoke else (512, 2048)
    data = jnp.asarray(RNG.normal(size=(R, 32)), jnp.float32)
    cols = jnp.asarray(RNG.integers(0, N, (R, 32)), jnp.int32)
    x = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    t_k = time_fn(lambda: spmv_ell(data, cols, x, interpret=interpret).block_until_ready(),
                  iters=iters)
    t_r = time_fn(lambda: ref.spmv_ell(data, cols, x).block_until_ready(), iters=iters)
    emit(f"kernel/spmv_ell/{mode}", t_k, f"ref_us={t_r:.1f}")

    # spmm: same ELL block, multi-vector rhs
    for k in (4,) if smoke else (4, 64):
        X = jnp.asarray(RNG.normal(size=(N, k)), jnp.float32)
        t_k = time_fn(lambda: spmm_ell(data, cols, X, interpret=interpret).block_until_ready(),
                      iters=iters)
        t_r = time_fn(lambda: ref.spmm_ell(data, cols, X).block_until_ready(),
                      iters=iters)
        emit(f"kernel/spmm_ell/{mode}/k{k}", t_k, f"ref_us={t_r:.1f}")

    # flash attention
    S = 64 if smoke else 256
    q = jnp.asarray(RNG.normal(size=(1, S, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, S, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, S, 2, 64)), jnp.float32)
    t_k = time_fn(lambda: flash_attention_kernel(q, k, v, block_q=32 if smoke else 128,
                                                 block_k=32 if smoke else 128,
                                                 interpret=interpret).block_until_ready(),
                  iters=min(iters, 5))
    t_r = time_fn(lambda: ref.attention(q[0], k[0], v[0]).block_until_ready(),
                  iters=iters)
    emit(f"kernel/flash_attention/{mode}", t_k, f"ref_us={t_r:.1f}")

    # ssd
    S = 128 if smoke else 512
    xs = jnp.asarray(RNG.normal(size=(2, S, 4, 32)), jnp.float32)
    loga = jnp.asarray(-np.abs(RNG.normal(size=(2, S, 4))) * 0.2, jnp.float32)
    b = jnp.asarray(RNG.normal(size=(2, S, 32)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(2, S, 32)), jnp.float32)
    t_k = time_fn(lambda: ssd_scan_kernel(xs, loga, b, c, chunk=64 if smoke else 128,
                                          interpret=interpret).block_until_ready(),
                  iters=min(iters, 5))
    t_r = time_fn(lambda: ssd_chunked(xs, loga, b, c,
                                      chunk=64 if smoke else 128).block_until_ready(),
                  iters=min(iters, 5))
    emit(f"kernel/ssd_scan/{mode}", t_k, f"xla_chunked_us={t_r:.1f}")


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
