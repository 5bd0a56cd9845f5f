"""Jax-free distributed SpMV executor for the solver test/benchmark path.

:class:`NumpySpMV` runs the SAME planned stage programs as the device
executor -- the plan comes from the module-level plan cache
(:func:`repro.comm.strategies.planned`), the exchange runs through
:func:`repro.comm.exchange.execute_numpy` (the bit-exact numpy oracle of the
``shard_map`` executor), and the local compute is the blocked-ELL
contraction in plain numpy.  Because every strategy delivers the identical
canonical halo buffer, a Krylov solve on this operator produces
*bitwise-identical* residual histories across strategies and across
barrier-vs-split-phase execution -- the property pinned by
``tests/test_solver.py``.

``overlap=True`` exercises the split-phase decomposition: the pattern is
factored through the module ``_SPLIT_CACHE``
(:func:`repro.comm.strategies._split_phase_cached`, visible as
``split_hits``/``split_misses`` in :func:`repro.comm.cache_stats`), the
on-pod and inter-pod sub-plans execute separately, and
:func:`repro.comm.exchange.merge_split_phase` reassembles the halo --
bit-identical to the barrier buffer, so the local compute needs no masking
to stay bit-compatible (unlike the device pipeline, nothing actually runs
concurrently here; the decomposition is what is being exercised).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.comm import faults as faults_mod
from repro.comm import strategies as comm_strategies
from repro.comm import wire as wire_mod
from repro.comm.exchange import execute_numpy, merge_split_phase
from repro.comm.topology import PodTopology
from repro.sparse.partition import SpmvPartition
from repro.trace import scope


def _ell_matvec(data: np.ndarray, cols: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Blocked-ELL contraction over stacked ranks.

    ``data``/``cols``: ``[g, L, K]``; ``x``: ``[g, W]`` (per-rank source
    vector or halo buffer).  Padding slots have ``data == 0, cols == 0`` and
    contribute exact zeros.
    """
    g = x.shape[0]
    gathered = x[np.arange(g)[:, None, None], cols]  # [g, L, K]
    return (data * gathered).sum(axis=2)


@dataclasses.dataclass
class NumpySpMV:
    """One matrix + topology + strategy, executed without jax.

    Mirrors :class:`repro.sparse.spmv.DistributedSpMV`'s call contract for
    vectors (``v [nranks, L] -> w [nranks, L]``) and shares its plan cache,
    so a solve on either operator re-plans nothing and the
    one-plan-per-solve property is measurable via
    ``repro.comm.cache_stats()``.
    """

    partition: SpmvPartition
    strategy: str = "standard"
    message_cap_bytes: int = 16384
    overlap: bool = False
    #: inter-pod wire codec (repro.comm.wire); "none" keeps the bitwise
    #: residual-history property across strategies, lossy codecs trade the
    #: pinned per-element halo error bound for 2-4x fewer DCI bytes
    wire: str = "none"
    #: opt-in wire integrity verification; a failed check engages the
    #: retry -> codec-demotion -> strategy-re-advise ladder
    #: (:func:`repro.comm.faults.run_ladder`)
    verify: bool = False
    #: seeded deterministic fault injection (repro.comm.faults.FaultPlan)
    faults: Optional[faults_mod.FaultPlan] = None
    #: shared health tracker; created on demand when verify/faults are set
    health: Optional[faults_mod.HealthTracker] = None
    max_retries: int = 1
    fallback: bool = True

    def __post_init__(self) -> None:
        if self.strategy not in comm_strategies.STRATEGY_NAMES:
            raise ValueError(
                f"unknown strategy {self.strategy!r}; "
                f"known: {comm_strategies.STRATEGY_NAMES}"
            )
        wire_mod.check_codec(self.wire)
        pattern = self.partition.pattern
        if self.overlap:
            sp, _ = comm_strategies._split_phase_cached(pattern)
            self._split = sp
            self._remote_plan = comm_strategies.planned(
                sp.remote, self.strategy, message_cap_bytes=self.message_cap_bytes
            )
            self._local_plan = comm_strategies.planned(sp.local, "local")
            self._plan = None
        else:
            self._split = None
            self._plan = comm_strategies.planned(
                pattern, self.strategy, message_cap_bytes=self.message_cap_bytes
            )
        g, L = self.topo.nranks, self.partition.rows_per_rank
        self._diag_d = self.partition.diag.data.reshape(g, L, -1)
        self._diag_c = self.partition.diag.cols.reshape(g, L, -1)
        self._off_d = self.partition.off.data.reshape(g, L, -1)
        self._off_c = self.partition.off.cols.reshape(g, L, -1)
        if self.health is None and (self.verify or self.faults is not None):
            self.health = faults_mod.HealthTracker()
        self._fault_calls = 0
        #: RecoveryPath.key of the most recent recovered exchange, or None
        self.last_recovery: Optional[str] = None

    @property
    def topo(self) -> PodTopology:
        return self.partition.topo

    @property
    def rows_per_rank(self) -> int:
        return self.partition.rows_per_rank

    # ------------------------------------------------------------------
    def halo(self, v: np.ndarray) -> np.ndarray:
        """Exchange only: ``[nranks, L] -> [nranks, H]`` canonical buffer.

        With ``verify`` or ``faults`` set, the exchange runs inside the
        recovery ladder; faults and checks ride the inter-pod (sub-)plan
        only, so on-pod data is never touched.
        """
        v = np.asarray(v)
        if self.faults is None and not self.verify:
            if self.overlap:
                # inter-pod and on-pod sub-plans execute separately, then
                # merge -- bit-identical to the unsplit plan
                # (tests/test_overlap.py); the wire codec rides the
                # inter-pod sub-plan only
                remote = execute_numpy(self._remote_plan, v, wire=self.wire)
                local = execute_numpy(self._local_plan, v)
                return merge_split_phase(self._split, local, remote)
            return execute_numpy(self._plan, v, wire=self.wire)
        return self._guarded_halo(v)

    def _exchange(self, v: np.ndarray, strategy: str, wire: str,
                  fault_call: int) -> np.ndarray:
        """One physical halo attempt under (strategy, wire) -- the ladder's
        probe; plans come from the module cache, so variants replan once."""
        if self.overlap:
            remote_plan = comm_strategies.planned(
                self._split.remote, strategy,
                message_cap_bytes=self.message_cap_bytes,
            )
            remote = execute_numpy(
                remote_plan, v, wire=wire, faults=self.faults,
                fault_call=fault_call, verify=self.verify,
            )
            local = execute_numpy(self._local_plan, v)
            return merge_split_phase(self._split, local, remote)
        plan = comm_strategies.planned(
            self.partition.pattern, strategy,
            message_cap_bytes=self.message_cap_bytes,
        )
        return execute_numpy(
            plan, v, wire=wire, faults=self.faults,
            fault_call=fault_call, verify=self.verify,
        )

    def _guarded_halo(self, v: np.ndarray) -> np.ndarray:
        def attempt(strategy: str, wire: str) -> np.ndarray:
            idx = self._fault_calls
            self._fault_calls += 1
            return self._exchange(v, strategy, wire, idx)

        out, path = faults_mod.run_ladder(
            attempt,
            strategy=self.strategy,
            wire=self.wire,
            health=self.health,
            max_retries=self.max_retries,
            fallback=self.fallback,
            choose_alternative=faults_mod.advise_alternative(
                self.partition.pattern
            ),
        )
        if path is not None:
            self.last_recovery = path.key
        return out

    def __call__(self, v: np.ndarray) -> np.ndarray:
        v = np.asarray(v)
        g, L = self.topo.nranks, self.partition.rows_per_rank
        if v.shape != (g, L):
            raise ValueError(f"expected [{g}, {L}], got {tuple(v.shape)}")
        halo = self.halo(v)
        return _ell_matvec(self._diag_d, self._diag_c, v) + _ell_matvec(
            self._off_d, self._off_c, halo
        )

    @property
    def wire_bytes(self):
        """(intra-pod, inter-pod) wire bytes of one exchange, codec-scaled."""
        if self.overlap:
            ri, rj = wire_mod.scaled_wire_bytes(self._remote_plan, self.wire)
            li, _ = wire_mod.scaled_wire_bytes(self._local_plan, "none")
            return (ri + li, rj)
        return wire_mod.scaled_wire_bytes(self._plan, self.wire)


def build_numpy(matrix, topo: PodTopology, strategy: str = "standard", **kw) -> NumpySpMV:
    """Partition ``matrix`` and wrap it in a :class:`NumpySpMV`."""
    from repro.sparse.partition import partition_csr

    return NumpySpMV(partition_csr(matrix, topo), strategy=strategy, **kw)


# ---------------------------------------------------------------------------
# Traceable operator (whole-solve fusion support)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class TraceableOperator:
    """A distributed SpMV as a pure per-shard callable + operand pytree.

    The matvec analogue of :class:`repro.comm.strategies.TraceableExchange`:
    :attr:`operands` is a flat tuple of ``[nranks, ...]`` device arrays
    (exchange plan arrays, split-phase merge maps, blocked-ELL data/cols,
    overlap phase masks) that a caller threads through its own ``shard_map``
    input specs, and :meth:`matvec` is the pure per-shard
    ``v [1, L] -> w [1, L]`` program -- exchange stages, (masked) blocked-ELL
    contraction and, under ``overlap``, the split-phase decomposition, all
    expressed inline so the whole matvec can live inside a traced loop body
    (:mod:`repro.solve.fused`).

    Build with :func:`traceable_operator` from either executor flavor
    (:class:`repro.sparse.spmv.DistributedSpMV` or :class:`NumpySpMV`).
    """

    topo: PodTopology
    local_size: int
    overlap: bool
    use_pallas: bool
    mesh: object
    #: barrier path: the unsplit exchange (``None`` under ``overlap``)
    exchange: Optional[object]
    #: overlap path: inter-pod + on-pod sub-exchanges (``None`` otherwise)
    remote: Optional[object]
    local: Optional[object]
    #: flat ``[nranks, ...]`` device arrays; feed each through a
    #: ``P(WORLD_AXES)`` spec and pass the per-shard slices to :meth:`matvec`
    operands: tuple
    #: static operand layout: plan-array counts of the (remote) exchange and
    #: the on-pod exchange (0 in barrier mode)
    n_exchange_ops: int
    n_local_ops: int

    @property
    def verifier(self):
        """The exchange whose integrity checks guard this operator (the
        unsplit plan in barrier mode, the inter-pod sub-plan under overlap),
        or ``None`` when no DCI hop is checked."""
        tx = self.exchange if not self.overlap else self.remote
        return tx if (tx is not None and tx.emit_checks) else None

    # -- per-shard kernels ---------------------------------------------
    def _full(self, data, cols, x):
        from repro.kernels import ops, ref

        if self.use_pallas:
            return ops.spmv_ell(data, cols, x)
        return ref.spmv_ell(data, cols, x)

    def _masked(self, data, cols, x, tiles, rows):
        from repro.kernels import ops, ref

        if self.use_pallas:
            return ops.spmv_ell(data, cols, x, tile_mask=tiles)
        return ref.spmv_ell_masked(data, cols, x, rows)

    # ------------------------------------------------------------------
    def matvec(self, v, *operands):
        """Pure per-shard matvec: ``v [1, L] -> w [1, L]``."""
        with scope("spmv"):
            w, _ = self._apply(v, operands, verified=False)
        return w

    def matvec_verified(self, v, *operands):
        """Like :meth:`matvec` but also returns the ``[n_checks]`` wire
        integrity violation vector of the DCI-crossing exchange (empty when
        nothing is checked); surface positives via
        ``self.verifier.raise_viols``."""
        with scope("spmv"):
            return self._apply(v, operands, verified=True)

    def _apply(self, v, operands, verified: bool):
        k = self.n_exchange_ops
        if not self.overlap:
            pa, (dd, dc, od, oc) = operands[:k], operands[k:]
            halo, viols = self._run_exchange(self.exchange, v, pa, verified)
            with scope("spmv.diag"):
                w_diag = self._full(dd[0], dc[0], v[0])
            with scope("spmv.off"):
                w_off = self._full(od[0], oc[0], halo[0])
            return (w_diag + w_off)[None], viols
        rpa = operands[:k]
        lpa = operands[k : k + self.n_local_ops]
        (
            mask, valid, li, ri, dd, dc, od, oc,
            all_tiles, all_rows, bnd_tiles, bnd_rows,
        ) = operands[k + self.n_local_ops :]
        # split-phase decomposition in-body: the inter-pod sub-exchange and
        # the halo-independent diag pass carry no data dependency, so XLA is
        # free to overlap them; the boundary-masked off pass waits on the
        # merged halo exactly like the host pipeline's finish()
        remote_out, viols = self._run_exchange(self.remote, v, rpa, verified)
        local_out = self.local.run(v, *lpa)
        halo = comm_strategies.merge_shard(
            mask, valid, li, ri, local_out, remote_out
        )
        with scope("spmv.diag"):
            w_diag = self._masked(dd[0], dc[0], v[0], all_tiles[0], all_rows[0])
        with scope("spmv.off"):
            w_off = self._masked(od[0], oc[0], halo[0], bnd_tiles[0], bnd_rows[0])
        return (w_diag + w_off)[None], viols

    @staticmethod
    def _run_exchange(tx, v, plan_arrays, verified: bool):
        import jax.numpy as jnp

        if verified and tx.emit_checks:
            return tx.run_verified(v, *plan_arrays)
        return tx.run(v, *plan_arrays), jnp.zeros((0,), jnp.float32)


def traceable_operator(op) -> TraceableOperator:
    """Lower either SpMV executor flavor to its traceable program value.

    Accepts a :class:`repro.sparse.spmv.DistributedSpMV` (reusing its plans,
    mesh, device blocks and kernel flavor) or a :class:`NumpySpMV` (blocks
    are transferred, the jnp-oracle kernels are used, and the mesh is the
    default exchange mesh).  Plans come from the same module caches as the
    host executors, so lowering an already-constructed operator re-plans
    nothing.  Every operand is placed on the mesh with one rank's slice per
    device.
    """
    from repro.comm.strategies import _default_mesh, traceable_exchange
    from repro.comm.topology import shard_ranks
    from repro.sparse.spmv import phase_masks, row_split

    part = op.partition
    topo, L = part.topo, part.rows_per_rank
    is_device = hasattr(op, "use_pallas")
    use_pallas = bool(getattr(op, "use_pallas", False))
    mesh = getattr(op, "mesh", None) or _default_mesh(topo)
    wire = op.wire
    verify = getattr(op, "verify", False)
    faults = getattr(op, "faults", None)

    if is_device:
        blocks = op._blocks
    else:
        blocks = tuple(
            shard_ranks(a, mesh)
            for a in (op._diag_d, op._diag_c, op._off_d, op._off_c)
        )

    if not op.overlap:
        if is_device:
            tx = op.exchange.traceable()
        else:
            tx = traceable_exchange(op._plan, codec=wire, verify=verify,
                                    faults=faults, mesh=mesh)
        return TraceableOperator(
            topo=topo, local_size=L, overlap=False, use_pallas=use_pallas,
            mesh=mesh, exchange=tx, remote=None, local=None,
            operands=tx.plan_arrays + blocks,
            n_exchange_ops=len(tx.plan_arrays), n_local_ops=0,
        )

    sp, _ = comm_strategies._split_phase_cached(part.pattern)
    remote_plan = comm_strategies.planned(
        sp.remote, op.strategy, message_cap_bytes=op.message_cap_bytes,
        fuse_program=getattr(op, "fuse_program", True),
    )
    local_plan = comm_strategies.planned(
        sp.local, "local", fuse_program=getattr(op, "fuse_program", True)
    )
    tx_remote = traceable_exchange(remote_plan, codec=wire, verify=verify,
                                   faults=faults, mesh=mesh)
    tx_local = traceable_exchange(local_plan, mesh=mesh)
    merge_ops = tuple(
        shard_ranks(a, mesh)
        for a in (sp.from_local, sp.valid, sp.local_idx, sp.remote_idx)
    )
    masks = op._masks if is_device else phase_masks(row_split(part), L, mesh)
    return TraceableOperator(
        topo=topo, local_size=L, overlap=True, use_pallas=use_pallas,
        mesh=mesh, exchange=None, remote=tx_remote, local=tx_local,
        operands=(
            tx_remote.plan_arrays + tx_local.plan_arrays + merge_ops
            + blocks + masks
        ),
        n_exchange_ops=len(tx_remote.plan_arrays),
        n_local_ops=len(tx_local.plan_arrays),
    )
