"""Flash attention (forward) Pallas kernel for TPU.

Online-softmax over K/V blocks with the accumulator, running max and running
denominator held in VMEM scratch across the (sequential, innermost) K-block
grid dimension -- the canonical TPU flash pattern:

* grid = (batch*heads, n_q_blocks, n_k_blocks); TPU iterates the minor grid
  dim sequentially, so scratch carries the online-softmax state.
* BlockSpecs tile Q/K/V into ``[BLOCK_Q, D]`` / ``[BLOCK_K, D]`` VMEM tiles;
  D and the block sizes are multiples of 128 so the QK^T and PV matmuls map
  onto the MXU.
* causal / sliding-window masking is applied per (q-block, k-block) tile from
  absolute positions (mask-only: TPU grids cannot skip iterations; the HLO
  cost of masked tiles is noted in DESIGN.md).
* GQA is handled in the BlockSpec index map: the KV block index derives from
  the query head id, so KV tiles are never materially repeated.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, seq_k: int, offset: int):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # [BQ, D]
    k = k_ref[0].astype(jnp.float32)  # [BK, D]
    v = v_ref[0].astype(jnp.float32)  # [BK, D]
    logits = q @ k.T * scale  # [BQ, BK]

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KV, D]
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    *,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, max(Sq, 16))
    block_k = min(block_k, max(Sk, 16))

    def pad_seq(x, blk):
        p = (-x.shape[1]) % blk
        return jnp.pad(x, ((0, 0), (0, p), (0, 0), (0, 0))) if p else x

    qp = pad_seq(q, block_q)
    kp = pad_seq(k, block_k)
    vp = pad_seq(v, block_k)
    Sqp, Skp = qp.shape[1], kp.shape[1]
    # head-major [B*H, S, D] layout
    qh = qp.transpose(0, 2, 1, 3).reshape(B * H, Sqp, D)
    kh = kp.transpose(0, 2, 1, 3).reshape(B * KV, Skp, D)
    vh = vp.transpose(0, 2, 1, 3).reshape(B * KV, Skp, D)

    def kv_index(bh, iq, ik):
        # query head bh = b*H + h  ->  kv row b*KV + h // rep
        return (bh // H) * KV + (bh % H) // rep, ik, 0

    grid = (B * H, Sqp // block_q, Skp // block_k)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_k=Sk, offset=Sk - Sq,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),  # acc
            pltpu.VMEM((block_q,), jnp.float32),  # running max
            pltpu.VMEM((block_q,), jnp.float32),  # running denom
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = out.reshape(B, H, Sqp, D).transpose(0, 2, 1, 3)
    return out[:, :Sq]
