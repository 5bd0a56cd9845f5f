"""Blocked-ELL SpMV / SpMM Pallas kernels for TPU.

:func:`spmv_ell` computes ``w[i] = sum_k data[i, k] * x[cols[i, k]]`` for an
ELL-padded sparse block (the local on-rank / off-rank SpMV of the paper's
distributed SpMV, §2.4); :func:`spmm_ell` is its multi-vector generalization
``W[i, c] = sum_k data[i, k] * X[cols[i, k], c]`` for a ``[N, C]`` right-hand
side (the fused local compute paired with the batched ``[nranks, L, k]``
halo exchange).

TPU adaptation (vs. a CUDA CSR kernel):

* CSR's per-row variable nnz maps badly onto the VPU's (8, 128) vregs; we use
  ELL padding so every row tile is a dense rectangle -- the padding slots
  carry ``data == 0`` so they contribute nothing.
* Rows ride the 128-wide lane axis: the kernel sees ``data`` as ``[K, R]``
  and the gathered source values as ``[K, R]`` (SpMV) or ``[K, C, R]``
  (SpMM), so the products and every output row tile are lane-dense.
* The gather ``x[cols]`` runs in XLA before the ``pallas_call``.  Mosaic
  lowers only ``take_along_axis``-shaped gathers (indices shaped like the
  operand), which cannot read an arbitrary ``x[cols]``; the XLA gather also
  keeps ``x`` out of VMEM, so neither kernel is bounded by the source
  vector's size.  It costs one ``K x C x R`` pass through HBM per call.
* The row dimension is tiled with a ``BlockSpec`` grid of ``TILE_R`` rows;
  SpMM adds a grid axis of ``TILE_C`` rhs columns (one sublane tile), so each
  step's working set is ``K x TILE_C x TILE_R`` whatever ``k`` is.

Reduction: both kernels sum the f32 products with one reduce over the K
axis.  Interpreted, that reduce runs in the same order for SpMV and for a
single-column SpMM, which therefore agree bit for bit (the tests pin it);
compiled, SpMV reduces across sublanes and SpMM across vregs, so the two may
differ in the last bit.

Row-tile masking (the split-phase/overlap hook):

* Both kernels accept an optional ``tile_mask`` -- one int per row tile,
  passed as a scalar-prefetch operand (all ones when omitted).  Inactive
  tiles (mask 0) skip the multiply-reduce via ``pl.when`` and deliver
  zeros, so both passes of the overlapped distributed SpMV reuse ONE
  kernel: the diag pass runs every row tile while the inter-node exchange
  is in flight, and the off pass afterwards reduces only the boundary tiles
  (interior tiles' off-block rows are pure padding).  The mask does *not*
  reach the XLA gather before the kernel: a masked call still gathers
  ``x[cols]`` for every row, inactive tiles included, so it saves the
  reduce but not the gather.  Masked and unmasked calls run the same kernel
  body, which is what makes the overlapped path bit-compatible with the
  barrier path.

Both kernels take ``interpret`` without a default: the main path reaches
them through :mod:`repro.kernels.ops`, which picks the mode from the backend.

Device scopes (:func:`repro.trace.scope`): ``spmv.layout`` on the
transposes, pads and output slice, ``spmv.gather`` on the XLA gather,
``spmv.kernel`` on the ``pallas_call``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.trace import scope

TILE_R = 2048  # rows per grid step (lanes)
TILE_C = 8  # rhs columns per SpMM grid step (one sublane tile)


def _ell_kernel(mask_ref, data_ref, g_ref, out_ref):
    # data [K, T]; g [K, T] -> out [T] (SpMV) or g [K, C, T] -> out [C, T];
    # products and the sum are f32 whatever the storage dtype
    spmm = len(g_ref.shape) == 3
    active = mask_ref[pl.program_id(0)] != 0

    @pl.when(active)
    def _active():
        d = data_ref[...].astype(jnp.float32)
        if spmm:
            d = d[:, None, :]
        prod = d * g_ref[...].astype(jnp.float32)
        out_ref[...] = prod.sum(axis=0).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(active))
    def _inactive():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _pad_to(a: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _transpose_blocks(data, cols):
    """``[R, K]`` ELL storage -> rows-on-lanes ``[K, Rp]``, zero-padded to
    whole row tiles (padding slots read ``x[0]`` with weight 0)."""
    return _pad_to(data.T, TILE_R, 1), _pad_to(cols.T, TILE_R, 1)


def num_row_tiles(rows: int) -> int:
    """Grid length (= ``tile_mask`` length) for ``rows`` ELL rows."""
    return -(-rows // TILE_R)


def _tile_mask(tile_mask, ntiles: int) -> jnp.ndarray:
    if tile_mask is None:
        # an all-ones mask the compiler cannot see: a constant one lets XLA
        # drop the tile branch of the interpreted kernel and compile its
        # body differently from a masked call's, so the two would no longer
        # agree bit for bit
        return jax.lax.optimization_barrier(jnp.ones((ntiles,), jnp.int32))
    if tile_mask.shape != (ntiles,):
        raise ValueError(
            f"tile_mask must have shape ({ntiles},) for this row count, "
            f"got {tuple(tile_mask.shape)}"
        )
    return tile_mask.astype(jnp.int32)


def _ell_call(data_t, g, grid, g_spec, out_spec, out_shape, tile_mask, interpret):
    """Run the row-tiled kernel over ``data_t [K, Rp]`` and gathered ``g``;
    the tile mask (all ones when ``None``) is a scalar-prefetch operand."""
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((data_t.shape[0], TILE_R), lambda i, *_: (0, i)), g_spec],
        out_specs=out_spec,
    )
    return pl.pallas_call(
        _ell_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, data_t.dtype),
        interpret=interpret,
    )(_tile_mask(tile_mask, grid[0]), data_t, g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_ell(
    data: jnp.ndarray,
    cols: jnp.ndarray,
    x: jnp.ndarray,
    *,
    interpret: bool,
    tile_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``w = A @ x`` for an ELL block. data/cols: [R, K]; x: [N] -> w: [R].

    ``tile_mask`` (optional ``[num_row_tiles(R)]`` ints) selects which row
    tiles reduce; inactive tiles deliver zeros (their rows are still
    gathered).
    """
    R = data.shape[0]
    with scope("spmv.layout"):
        data_t, cols_t = _transpose_blocks(data, cols)  # [K, Rp]
    with scope("spmv.gather"):
        g = x[cols_t]
    with scope("spmv.kernel"):
        out = _ell_call(
            data_t,
            g,
            grid=(num_row_tiles(R),),
            g_spec=pl.BlockSpec((data_t.shape[0], TILE_R), lambda i, *_: (0, i)),
            out_spec=pl.BlockSpec((TILE_R,), lambda i, *_: (i,)),
            out_shape=(data_t.shape[1],),
            tile_mask=tile_mask,
            interpret=interpret,
        )
    with scope("spmv.layout"):
        return out[:R]


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_ell(
    data: jnp.ndarray,
    cols: jnp.ndarray,
    x: jnp.ndarray,
    *,
    interpret: bool,
    tile_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``W = A @ X`` for an ELL block. data/cols: [R, K]; x: [N, C] -> [R, C].

    ``tile_mask`` (optional ``[num_row_tiles(R)]`` ints) selects which row
    tiles reduce; inactive tiles deliver zeros (their rows are still
    gathered).
    """
    R = data.shape[0]
    C = x.shape[1]
    tc = min(C, TILE_C)
    with scope("spmv.layout"):
        x_t = _pad_to(x, tc, 1).T  # [Cp, N]
        data_t, cols_t = _transpose_blocks(data, cols)  # [K, Rp]
    with scope("spmv.gather"):
        g = jnp.moveaxis(x_t[:, cols_t], 0, 1)  # [K, Cp, Rp]
    K, Cp, Rp = g.shape
    with scope("spmv.kernel"):
        out = _ell_call(
            data_t,
            g,
            grid=(num_row_tiles(R), Cp // tc),
            g_spec=pl.BlockSpec((K, tc, TILE_R), lambda i, c, *_: (0, c, i)),
            out_spec=pl.BlockSpec((tc, TILE_R), lambda i, c, *_: (c, i)),
            out_shape=(Cp, Rp),
            tile_mask=tile_mask,
            interpret=interpret,
        )
    with scope("spmv.layout"):
        return out[:C, :R].T
