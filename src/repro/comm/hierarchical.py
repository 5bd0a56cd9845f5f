"""Pod-aware hierarchical collectives (the paper's insight applied to the
regular collectives of LM training).

The paper's node-aware schemes concentrate inter-node traffic on the cheap
local fabric and minimize what crosses the expensive one.  For the *regular*
collectives of multi-pod training the same decomposition applies:

* all-reduce(pod x data)  ->  reduce-scatter(data/ICI)
                              -> all-reduce(pod/DCI, 1/|data| of the bytes)
                              -> all-gather(data/ICI)

Each chip then injects only ``bytes/|data|`` onto the inter-pod fabric --
exactly the Split strategy's "use all available on-node processes to
communicate inter-node data" (paper §4.6), with |data| playing the role of
PPN.  An optional int8 error-feedback compressor
(:mod:`repro.comm.compression`) further shrinks the DCI hop only, keeping
full precision on ICI.

These primitives run *inside* ``shard_map`` bodies.  :func:`sync_grads`
wraps a whole gradient pytree for data-parallel training loops.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import compression


def _flatten_pad(x: jnp.ndarray, n: int) -> Tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def psum_hierarchical(
    x: jnp.ndarray,
    outer_axis: str,
    inner_axis: str,
    compressor: Optional[compression.Compressor] = None,
    residual: Optional[jnp.ndarray] = None,
):
    """All-reduce over (outer x inner) as RS(inner) -> AR(outer) -> AG(inner).

    Must be called inside ``shard_map`` with both axes in scope.  Returns the
    reduced array (and the new compression residual if ``compressor``).
    """
    n_in = jax.lax.axis_size(inner_axis)
    flat, pad = _flatten_pad(x, n_in)
    shard = jax.lax.psum_scatter(
        flat.reshape(n_in, -1), inner_axis, scatter_dimension=0, tiled=False
    )
    new_residual = None
    if compressor is not None:
        if residual is not None:
            shard = shard + residual.reshape(shard.shape)
        q, scale = compressor.compress(shard, outer_axis)
        q_sum = jax.lax.psum(q.astype(jnp.int32), outer_axis)
        reduced = compressor.decompress(q_sum, scale)
        new_residual = (shard - compressor.decompress(q.astype(jnp.int32), scale)).reshape(-1)
    else:
        reduced = jax.lax.psum(shard, outer_axis)
    full = jax.lax.all_gather(reduced, inner_axis, axis=0, tiled=False).reshape(-1)
    if pad:
        full = full[:-pad]
    out = full.reshape(x.shape)
    if compressor is not None:
        return out, new_residual
    return out


def psum_flat(x: jnp.ndarray, outer_axis: str, inner_axis: str) -> jnp.ndarray:
    """Baseline: one flat all-reduce over the joint axis (standard comm)."""
    return jax.lax.psum(x, (outer_axis, inner_axis))


def dot_hierarchical(
    x: jnp.ndarray,
    y: jnp.ndarray,
    outer_axis: str,
    inner_axis: str,
    compressor: Optional[compression.Compressor] = None,
) -> jnp.ndarray:
    """Global ``<x, y>`` over (outer x inner)-sharded leaves, node-aware.

    The paper's decomposition applied to the scalar reductions of a Krylov
    solver: each chip reduces its shard locally, the partial sums reduce over
    the cheap on-pod fabric (ICI) first, and exactly ONE scalar per pod
    crosses the expensive inter-pod hop -- the 3-Step shape (fuse on-node,
    minimize inter-node) degenerated to a reduction tree.  Must be called
    inside ``shard_map`` with both axes in scope.

    ``compressor`` int8-quantizes the per-pod partial on the inter-pod hop
    only (ICI stays full precision).  For a scalar this saves 3 bytes and
    costs ~``1/(2*qmax)`` relative error per reduction, so it exists to keep
    the solver's reduction path byte-compatible with the compressed gradient
    path, not as a bandwidth optimization -- leave it off when bitwise
    reduction accuracy matters (it perturbs Krylov convergence).
    """
    part = jnp.sum(x * y)
    part = jax.lax.psum(part, inner_axis)  # on-pod tree, full precision
    if compressor is None:
        return jax.lax.psum(part, outer_axis)
    q, scale = compressor.compress(part[None], outer_axis)
    q_sum = jax.lax.psum(q.astype(jnp.int32), outer_axis)
    return compressor.decompress(q_sum, scale)[0]


def all_gather_hierarchical(x: jnp.ndarray, outer_axis: str, inner_axis: str) -> jnp.ndarray:
    """All-gather over (outer x inner): AG(outer/DCI) then AG(inner/ICI).

    Gathering the small per-chip shard across pods first minimizes DCI bytes;
    the fan-out to full size happens on ICI.
    """
    x = jax.lax.all_gather(x, outer_axis, axis=0, tiled=True)
    return jax.lax.all_gather(x, inner_axis, axis=0, tiled=True)


def all_to_all_hierarchical(
    x: jnp.ndarray, outer_axis: str, inner_axis: str
) -> jnp.ndarray:
    """All-to-all over the joint (outer x inner) axis, decomposed 3-Step-style.

    ``x`` has leading dim ``n_out * n_in`` (one block per destination device,
    destination-major ``(outer, inner)``).  Step 1 fuses all blocks bound for
    the same destination pod and moves them in one inter-pod exchange
    (a2a over outer); step 2 redistributes within the destination pod
    (a2a over inner).  Equivalent to a flat all_to_all over the joint axis but
    with pod-fused inter-pod messages (the 3-Step/2-Step hybrid the paper
    calls 2-Step when every chip stays active).
    """
    n_out = jax.lax.axis_size(outer_axis)
    n_in = jax.lax.axis_size(inner_axis)
    blk = x.shape[0] // (n_out * n_in)
    rest = x.shape[1:]
    # [n_out, n_in * blk, ...]: fuse per destination pod
    y = x.reshape(n_out, n_in * blk, *rest)
    y = jax.lax.all_to_all(y, outer_axis, split_axis=0, concat_axis=0, tiled=True)
    # now [n_out * n_in * blk]: block (q, j) = from (q, me) to (mypod, j)
    y = y.reshape(n_out, n_in, blk, *rest).transpose(1, 0, *range(2, 3 + len(rest)))
    y = y.reshape(n_in, n_out * blk, *rest)
    y = jax.lax.all_to_all(y, inner_axis, split_axis=0, concat_axis=0, tiled=True)
    # [n_in, n_out, blk] -> destination-major (outer, inner)
    y = y.reshape(n_in, n_out, blk, *rest).transpose(1, 0, *range(2, 3 + len(rest)))
    return y.reshape(n_out * n_in * blk, *rest)


# ---------------------------------------------------------------------------
# Gradient-tree synchronisation for data-parallel loops
# ---------------------------------------------------------------------------


def init_residuals(grads, inner_size: int):
    """Zero error-feedback residuals matching :func:`sync_grad_tree`'s shards."""
    return jax.tree.map(
        lambda g: jnp.zeros((-(-g.size // inner_size),), g.dtype), grads
    )


def sync_grad_tree(
    grads,
    outer_axis: str = "pod",
    inner_axis: str = "data",
    mode: str = "hierarchical",
    compressor: Optional[compression.Compressor] = None,
    residuals=None,
):
    """Average a gradient pytree over the DP axes (call inside ``shard_map``).

    ``grads`` leaves are this device's local-batch gradients; returns the
    global average.  ``mode`` is "flat" (standard, one joint all-reduce) or
    "hierarchical" (paper technique).  With ``compressor``, returns
    ``(grads, new_residuals)`` implementing error feedback on the DCI hop.
    """
    ndev = jax.lax.axis_size(outer_axis) * jax.lax.axis_size(inner_axis)

    def one(leaf, res):
        if mode == "flat":
            return jax.lax.psum(leaf, (outer_axis, inner_axis)) / ndev, res
        if compressor is not None:
            out, new_res = psum_hierarchical(
                leaf, outer_axis, inner_axis, compressor, res
            )
            return out / ndev, new_res
        return psum_hierarchical(leaf, outer_axis, inner_axis) / ndev, res

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = (
        jax.tree.flatten(residuals)[0]
        if residuals is not None
        else [None] * len(flat_g)
    )
    outs = [one(a, b) for a, b in zip(flat_g, flat_r)]
    new_g = jax.tree.unflatten(tdef, [o[0] for o in outs])
    if compressor is not None:
        new_r = jax.tree.unflatten(tdef, [o[1] for o in outs])
        return new_g, new_r
    return new_g
