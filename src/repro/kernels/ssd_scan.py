"""Mamba-2 chunked SSD Pallas kernel for TPU.

The SSD recurrence ``h_t = a_t h_{t-1} + b_t (x) x_t``, ``y_t = c_t . h_t``
is blocked into chunks of length Q: within a chunk the output is a masked,
decay-weighted ``[Q, Q]`` matmul (MXU work); across chunks a state of shape
``[N, P]`` per (batch, head) is carried in VMEM scratch through the
sequential innermost grid dimension -- the same scratch-carry pattern as the
flash kernel, which is how TPU expresses the paper-style "linear scan with
quadratic tiles" decomposition of SSD.

Grid: (B, H, n_chunks); chunk tensors (x [Q, P], b/c [Q, N], loga [Q]) are
VMEM tiles; Q/N/P sized in multiples of the 128 lane width where possible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # [Q, P]
    la = jnp.cumsum(loga_ref[0, 0].astype(jnp.float32), axis=0)  # [Q]
    b = b_ref[0].astype(jnp.float32)  # [Q, N]
    c = c_ref[0].astype(jnp.float32)  # [Q, N]
    h = h_ref[...]  # [N, P]

    # intra-chunk: masked decay-weighted attention-like matmul
    scores = c @ b.T  # [Q, Q]
    diff = la[:, None] - la[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (chunk, chunk), 1
    )
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
    y = (scores * decay) @ x  # [Q, P]
    # inter-chunk: incoming state decayed through each position
    y = y + jnp.exp(la)[:, None] * (c @ h)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update to chunk end
    w = jnp.exp(la[-1] - la)  # [Q]
    h_ref[...] = h * jnp.exp(la[-1]) + (b * w[:, None]).T @ x


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_scan_kernel(
    xdt: jnp.ndarray,  # [B, S, H, P] float32 (dt-scaled inputs)
    loga: jnp.ndarray,  # [B, S, H]
    b: jnp.ndarray,  # [B, S, N]
    c: jnp.ndarray,  # [B, S, N]
    chunk: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    B, S, H, P = xdt.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        loga = jnp.pad(loga, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q
    # head-major layouts: x [B, H, S, P]; loga [B, H, S]; b/c [B, S, N]
    xh = xdt.transpose(0, 2, 1, 3)
    lh = loga.transpose(0, 2, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=Q),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, Q), lambda ib, ih, ic: (ib, ih, ic)),
            pl.BlockSpec((1, Q, N), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, Q, N), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xh, lh, b, c)
    return out.transpose(0, 2, 1, 3)[:, :S]
