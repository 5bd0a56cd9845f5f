"""Node-aware reductions for the Krylov solvers.

Every dot product / norm inside :mod:`repro.solve.krylov` goes through one of
these backends so the solver's scalar traffic follows the paper's hierarchy:
reduce on the cheap on-pod fabric first, cross the expensive inter-pod hop
exactly once per pod.

* :class:`DeviceReductions` -- jitted ``shard_map`` program over the exchange
  mesh calling :func:`repro.comm.hierarchical.dot_hierarchical` (optionally
  int8-compressed on the inter-pod hop via
  :class:`repro.comm.compression.Compressor`).  This is the serving-path
  deployment of the hierarchical-collective layer that previously only the
  LM-training loop used.
* :class:`NumpyReductions` -- jax-free twin with the SAME summation tree
  (rank partials -> per-pod sums -> global sum) in float64.  Deterministic,
  so residual histories on the numpy executor are bitwise reproducible.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.comm import compression
from repro.comm.topology import LOCAL_AXIS, POD_AXIS, WORLD_AXES, PodTopology
from repro.trace import scope


@dataclasses.dataclass(frozen=True)
class NumpyReductions:
    """Hierarchical dot products in numpy (rank -> pod -> world order).

    Partials are accumulated in float64 regardless of the vector dtype: the
    solver's scalars (step sizes, residual norms) live at host level and the
    extra precision costs nothing while keeping float32 operands convergent
    to tight tolerances.
    """

    topo: PodTopology

    def dot(self, x: np.ndarray, y: np.ndarray) -> float:
        """``<x, y>`` for ``[nranks, L]`` operands, hierarchical order."""
        x = np.asarray(x, dtype=np.float64)
        y = np.asarray(y, dtype=np.float64)
        part = (x * y).reshape(self.topo.nranks, -1).sum(axis=1)  # per rank
        pods = part.reshape(self.topo.npods, self.topo.ppn).sum(axis=1)
        return float(pods.sum())

    def norm(self, x: np.ndarray) -> float:
        return float(np.sqrt(max(self.dot(x, x), 0.0)))


def traceable_dot(compressor: Optional[compression.Compressor] = None):
    """Per-shard hierarchical dot product for embedding in traced programs.

    Returns a pure callable ``dot(x, y) -> scalar`` over per-shard ``[1, L]``
    operands -- the exact reduction tree :class:`DeviceReductions` wraps in
    its own ``shard_map`` (rank partial, on-pod ``psum``, one inter-pod hop,
    optionally int8-compressed), but exposed raw so a fused solver can call
    it inside a ``lax.while_loop`` body without leaving the trace.  The
    result is replicated across shards.
    """
    from repro.comm.hierarchical import dot_hierarchical

    def dot(x, y):
        with scope("solve.reduce"):
            return dot_hierarchical(x[0], y[0], POD_AXIS, LOCAL_AXIS, compressor)

    return dot


class DeviceReductions:
    """Hierarchical dot products as a jitted ``shard_map`` collective.

    One compiled program per instance: ``[nranks, L] x [nranks, L] -> scalar``
    where each chip reduces its shard, the partials all-reduce over the
    on-pod axis, and one scalar per pod crosses the inter-pod axis
    (:func:`repro.comm.hierarchical.dot_hierarchical`).

    ``compressor`` quantizes the inter-pod hop int8 (error ~0.4% per
    reduction -- documented as perturbing Krylov convergence; keep it off
    unless the surrounding system already runs compressed reductions).
    """

    def __init__(
        self,
        topo: PodTopology,
        mesh=None,
        compressor: Optional[compression.Compressor] = None,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.comm.strategies import _default_mesh

        self.topo = topo
        self.mesh = mesh if mesh is not None else _default_mesh(topo)
        self.compressor = compressor
        shard_dot = traceable_dot(compressor)

        def body(x, y):
            return jnp.reshape(shard_dot(x, y), (1, 1))

        self._fn = jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(WORLD_AXES), P(WORLD_AXES)),
                out_specs=P(WORLD_AXES),
            )
        )

    def dot(self, x, y) -> float:
        """``<x, y>`` for ``[nranks, L]`` operands (every rank's copy of the
        replicated result is identical; rank 0's is returned)."""
        return float(np.asarray(self._fn(x, y))[0, 0])

    def norm(self, x) -> float:
        return float(np.sqrt(max(self.dot(x, x), 0.0)))

    def traceable(self):
        """This backend's reduction tree as a pure per-shard callable
        (:func:`traceable_dot` with the same compressor)."""
        return traceable_dot(self.compressor)


def default_reductions(op) -> "NumpyReductions | DeviceReductions":
    """Pick the reduction backend matching an operator's executor.

    :class:`repro.sparse.spmv.DistributedSpMV` gets the device collectives
    (on its own mesh); anything else -- notably the jax-free
    :class:`repro.solve.operator.NumpySpMV` -- gets the numpy twin.
    """
    from repro.sparse.spmv import DistributedSpMV

    if isinstance(op, DistributedSpMV):
        return DeviceReductions(op.topo, mesh=op.mesh)
    return NumpyReductions(op.topo)
