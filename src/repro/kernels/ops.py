"""Public wrappers for the Pallas kernels: the one place the mode is chosen.

On a TPU the kernels compile with Mosaic; on any other backend they run in
the Pallas interpreter, which is how the CPU tests check them against
:mod:`repro.kernels.ref`.  A kernel that Mosaic refuses raises: nothing here
falls back to the reference.
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import spmv_ell as _ell
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def spmv_ell(data, cols, x, tile_mask=None):
    """Blocked-ELL SpMV: ``w[i] = sum_k data[i,k] * x[cols[i,k]]``."""
    return _ell.spmv_ell(data, cols, x, interpret=_interpret(), tile_mask=tile_mask)


def spmm_ell(data, cols, x, tile_mask=None):
    """Blocked-ELL SpMM: ``W[i,c] = sum_k data[i,k] * x[cols[i,k], c]``."""
    return _ell.spmm_ell(data, cols, x, interpret=_interpret(), tile_mask=tile_mask)


def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None):
    """Blocked online-softmax attention; q [B,Sq,H,D], k/v [B,Sk,KV,D]."""
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, scale=scale, interpret=_interpret()
    )


def ssd_chunked(xdt, loga, b, c, chunk: int = 128):
    """Mamba-2 SSD over chunks (matches repro.models.ssd.ssd_chunked)."""
    return ssd_scan_kernel(xdt, loga, b, c, chunk=chunk, interpret=_interpret())
