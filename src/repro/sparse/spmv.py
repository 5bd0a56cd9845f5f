"""Distributed SpMV/SpMM with pluggable node-aware communication (paper §2.4, §5).

``A`` is row-partitioned over the mesh; each step is

    halo = exchange(v)                      # irregular p2p, chosen strategy
    w    = A_diag @ v_local + A_off @ halo  # local blocked-ELL SpMV

The exchange is an :class:`repro.comm.strategies.IrregularExchange` planned by
the selected strategy; ``strategy="auto"`` asks the model-driven advisor
(paper §4.6) to pick, with ``payload_width`` feeding the advisor's batched
byte terms.  The local compute runs the Pallas blocked-ELL kernels (compiled
on a TPU, interpreted elsewhere; :mod:`repro.kernels.ops` decides) or their
jnp oracles.

Multi-vector products (``V: [nranks, L, k]``) are first-class: one exchange
moves all ``k`` columns under the single cached plan and one fused blocked-ELL
SpMM replaces the per-column Python loop (:meth:`DistributedSpMV.matmat`).

``overlap=True`` replaces the barrier step with the split-phase pipeline
(paper §4.6 closing discussion: hide inter-node latency behind on-node work):

    handle = exchange.start(v)   # inter-pod phase in flight; on-pod done
    w_diag = A_diag @ v_local    # halo-independent: every row tile overlaps
    halo   = handle.finish()
    w_off  = A_off @ halo        # boundary row tiles only
    w      = w_diag + w_off

The boundary row set -- rows whose off-rank ELL row holds a *stored* entry
(structural ``off_row_nnz``, value-independent) -- comes from
:func:`repro.core.split_plan.split_rows` at kernel row-tile granularity;
interior tiles' off-block is pure padding and is skipped outright.  (Note
the off-rank block covers *all* non-owned columns, on-pod and inter-pod
alike, so even rows that only read on-pod neighbours count as boundary and
wait for ``finish()``.)  Both passes run the same tile-masked blocked-ELL
kernel, so with the Pallas kernels (the default) the overlapped result is
bit-identical to the barrier result for every strategy; the jnp-oracle
flavor (``use_pallas=False``) agrees to ~1 ulp because XLA fuses the
barrier program's two reductions.  Finite inputs are assumed, as everywhere
in the ELL layout: a padding slot computes ``0 * x[0]``, so a non-finite
value in slot 0 would poison padded rows in the barrier path but not in
the skipped interior tiles.

The local-compute programs are compiled once per
``(pattern fingerprint, payload width k, kernel flavor, mesh)`` into a
module-level LRU shared with the exchange plan/executor caches -- inspect via
``repro.comm.cache_stats()`` (``compute_hits`` / ``compute_misses``): distinct
``k`` widths get distinct compile entries while the exchange keeps exactly one
plan entry per pattern fingerprint.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm import strategies as comm_strategies
from repro.comm.strategies import IrregularExchange
from repro.comm.topology import (
    WORLD_AXES,
    PodTopology,
    make_exchange_mesh,
    shard_ranks,
)
from repro.core.advisor import EXECUTABLE_STRATEGY, advise
from repro.core.perfmodel import Strategy, Transport
from repro.core.split_plan import RowPhaseSplit, split_rows
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.spmv_ell import TILE_R
from repro.sparse.matrices import CSRMatrix
from repro.sparse.partition import SpmvPartition, partition_csr
from repro.trace import scope, span

#: advisor Strategy -> executable strategy name (canonical copy lives with
#: the advisor so the fault ladder's re-advising shares one mapping)
_ADVISED = EXECUTABLE_STRATEGY

# ---------------------------------------------------------------------------
# Local-compute compile cache
# ---------------------------------------------------------------------------

#: jitted local-compute programs keyed by
#: ``(pattern fingerprint, width, use_pallas, mesh)`` where ``width`` is the
#: payload column count ``k`` (``None`` = the unbatched SpMV program).  One
#: entry per (fingerprint, k): repeated construction / repeated ``matmat(k)``
#: calls reuse the jitted program, and new widths never evict the exchange's
#: single per-fingerprint plan entry.
_COMPUTE_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
COMPUTE_CACHE_MAX = 64
comm_strategies.register_cache(_COMPUTE_CACHE)


def _compute_program(
    fingerprint: str,
    mesh: jax.sharding.Mesh,
    use_pallas: bool,
    width: Optional[int],
):
    """Build (or fetch) the jitted shard_map local-compute program.

    ``width=None`` is the vector program (``v: [nranks, L]``); ``width=k``
    is the fused SpMM program (``V: [nranks, L, k]``).
    """
    key = (fingerprint, width, use_pallas, comm_strategies._mesh_key(mesh))

    def build():
        if width is None:
            def local(data, cols, x):
                if use_pallas:
                    return ops.spmv_ell(data, cols, x)
                return kref.spmv_ell(data, cols, x)
        else:
            def local(data, cols, x):
                if use_pallas:
                    return ops.spmm_ell(data, cols, x)
                return kref.spmm_ell(data, cols, x)

        def compute(v_local, halo, dd, dc, od, oc):
            # leading rank dim is 1 inside shard_map
            v_local, halo = v_local[0], halo[0]
            with scope("spmv"):
                with scope("spmv.diag"):
                    w_diag = local(dd[0], dc[0], v_local)
                with scope("spmv.off"):
                    w_off = local(od[0], oc[0], halo)
                return (w_diag + w_off)[None]

        return jax.jit(
            jax.shard_map(
                compute,
                mesh=mesh,
                in_specs=(P(WORLD_AXES),) * 6,
                out_specs=P(WORLD_AXES),
                check_vma=False,  # pallas_call does not yet annotate vma
            )
        )

    return comm_strategies.compute_cached(
        _COMPUTE_CACHE, key, COMPUTE_CACHE_MAX, build
    )


def _phase_program(
    fingerprint: str,
    mesh: jax.sharding.Mesh,
    use_pallas: bool,
    width: Optional[int],
):
    """Build (or fetch) the tile-masked one-block program of the overlapped
    local compute: ``x, (data, cols), masks -> block @ x`` on active tiles.

    The split-phase pipeline runs it twice per step: once for the
    halo-independent diag block (every row tile, while the inter-node
    exchange is in flight) and once for the halo-dependent off block after
    ``handle.finish()``, masked to the boundary row tiles (an interior
    tile's off-block rows are pure padding, so skipping them changes
    nothing).  Both runs use the SAME blocked-ELL kernel as the barrier
    path and the final ``diag + off`` add matches the barrier program's
    summation, so the overlapped result is bit-identical to it with the
    Pallas kernels (the jnp oracle agrees to ~1 ulp; see module docstring).
    """
    key = (fingerprint, width, use_pallas, "phase", comm_strategies._mesh_key(mesh))

    def build():
        if width is None:
            def local(data, cols, x, tiles, rows):
                if use_pallas:
                    return ops.spmv_ell(data, cols, x, tile_mask=tiles)
                return kref.spmv_ell_masked(data, cols, x, rows)
        else:
            def local(data, cols, x, tiles, rows):
                if use_pallas:
                    return ops.spmm_ell(data, cols, x, tile_mask=tiles)
                return kref.spmm_ell_masked(data, cols, x, rows)

        def compute(x, data, cols, tiles, rows):
            with scope("spmv"):
                return local(data[0], cols[0], x[0], tiles[0], rows[0])[None]

        return jax.jit(
            jax.shard_map(
                compute,
                mesh=mesh,
                in_specs=(P(WORLD_AXES),) * 5,
                out_specs=P(WORLD_AXES),
                check_vma=False,
            )
        )

    return comm_strategies.compute_cached(
        _COMPUTE_CACHE, key, COMPUTE_CACHE_MAX, build
    )


def row_split(part: SpmvPartition) -> RowPhaseSplit:
    """Interior/boundary row split (the overlap enabler) at the kernels' row
    tile.

    Classification is *structural*: a row is boundary iff its off-rank ELL
    row holds at least one stored entry (``off_row_nnz > 0``), so an
    explicitly stored zero still counts as a halo dependency and the split
    never depends on matrix values.
    """
    g, L = part.topo.nranks, part.rows_per_rank
    return split_rows(part.off_row_nnz.reshape(g, L) > 0, TILE_R)


def phase_masks(split: RowPhaseSplit, L: int, mesh: jax.sharding.Mesh) -> tuple:
    """The overlapped passes' masks, placed on ``mesh``: the all-tiles pair
    (the diag pass) and the boundary pair (the off pass), each as
    (tile mask, tile-expanded row mask)."""
    g, ntiles = split.boundary_tiles.shape
    bnd = split.boundary_tiles
    bnd_rows = np.repeat(bnd, split.tile_rows, axis=1)[:, :L]
    masks = (
        np.ones((g, ntiles), np.int32),
        np.ones((g, L), bool),
        bnd.astype(np.int32),
        bnd_rows,
    )
    return tuple(shard_ranks(m, mesh) for m in masks)


@dataclasses.dataclass
class DistributedSpMV:
    """A compiled distributed SpMV/SpMM for one matrix, topology and strategy.

    ``payload_width`` is the expected multi-vector column count ``k`` fed to
    the advisor when ``strategy="auto"`` -- larger widths amortize per-message
    latency and can flip the advised strategy into the bandwidth-bound regime.
    Any width can still be executed regardless of the advised-time value.

    ``overlap=True`` switches ``__call__``/:meth:`matmat` to the split-phase
    pipeline: the exchange runs as ``start()``/``finish()``
    (:meth:`repro.comm.strategies.IrregularExchange.start`), the whole
    halo-independent diag-block product computes while the inter-node phase
    is in flight, and only the boundary row tiles' off-block product (see
    :func:`repro.core.split_plan.split_rows`) runs after ``finish()``.
    Results are bit-compatible with the barrier path for every strategy.

    ``wire`` selects the exchange's inter-pod codec
    (:data:`repro.comm.wire.WIRE_CODECS`): halo values arriving from other
    pods carry the codec's pinned error bound while on-pod halo values stay
    full precision; ``wire="none"`` (the default) is bitwise identical to
    the codec-free path.  ``wire="auto"`` lets the advisor rank
    ``+wire:<codec>`` variants and picks the codec jointly with the
    strategy (``strategy="auto"``) or the fastest codec for a fixed
    strategy.

    Example (needs >= ``topo.nranks`` devices, e.g. via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)::

        import numpy as np
        from repro.comm import PodTopology
        from repro.sparse import build, thermal_like

        A = thermal_like(256, np.random.default_rng(0))
        topo = PodTopology(npods=2, ppn=4)
        sp = build(A, topo, strategy="auto", payload_width=8, overlap=True)

        V = np.ones((A.n, 8), np.float32)          # 8 right-hand sides
        W = sp.matmat(V.reshape(topo.nranks, -1, 8))  # ONE exchange, overlapped
    """

    partition: SpmvPartition
    strategy: str = "auto"
    message_cap_bytes: int = 16384
    use_pallas: bool = True
    mesh: Optional[jax.sharding.Mesh] = None
    fuse_program: bool = True
    payload_width: int = 1
    overlap: bool = False
    wire: str = "none"
    #: opt-in wire integrity verification on the exchange (see
    #: :class:`repro.comm.strategies.IrregularExchange`)
    verify: bool = False
    #: seeded deterministic fault injection (repro.comm.faults.FaultPlan)
    faults: Optional[object] = None
    #: shared health tracker for the recovery ladder / watchdog
    health: Optional[object] = None

    def __post_init__(self) -> None:
        with span("build"):
            self._build()

    def _build(self) -> None:
        """Advise, plan the exchange, fetch the compiled programs and place
        the blocks: host span ``repro.build``, with ``.advise`` and
        ``.place``."""
        topo = self.partition.topo
        if self.strategy == "auto" or self.wire == "auto":
            with span("build.advise"):
                advice = advise(
                    self.partition.pattern.to_comm_pattern(),
                    machine="tpu_v5e_pod",
                    payload_width=self.payload_width,
                    # "auto" ranks every codec; a fixed codec constrains the
                    # candidate set; "none" keeps the paper's ranking
                    wire="auto" if self.wire == "auto" else (
                        None if self.wire == "none" else self.wire
                    ),
                )
                self.advice = advice
                best = advice.best
                if self.strategy != "auto":
                    # wire="auto" with a pinned strategy: fastest codec among
                    # this strategy's own variants
                    best = next(
                        (
                            r for r in advice.ranked
                            if _ADVISED[r.strategy] == self.strategy
                        ),
                        None,
                    )
                    if best is None:
                        raise ValueError(
                            f"unknown strategy {self.strategy!r}; known: "
                            f"{sorted(set(_ADVISED.values()))}"
                        )
                self.strategy = _ADVISED[best.strategy]
                if self.wire == "auto":
                    self.wire = best.wire
        else:
            self.advice = None
        if self.mesh is None:
            self.mesh = make_exchange_mesh(topo)
        # The exchange's plan + jitted executor and the local-compute programs
        # all come from module-level caches (repro.comm.strategies plus
        # _COMPUTE_CACHE above), so rebuilding for the same matrix partition
        # skips planning and every jit.
        self.exchange = IrregularExchange(
            self.partition.pattern,
            self.strategy,
            mesh=self.mesh,
            message_cap_bytes=self.message_cap_bytes,
            fuse_program=self.fuse_program,
            wire=self.wire,
            verify=self.verify,
            faults=self.faults,
            health=self.health,
        )
        # the exchange owns (and may have created) the shared tracker
        self.health = self.exchange.health
        L = self.partition.rows_per_rank
        g = topo.nranks

        part = self.partition
        self._fingerprint = part.pattern.fingerprint()
        self._compute = _compute_program(
            self._fingerprint, self.mesh, self.use_pallas, None
        )
        # each device holds only its rank's slice of the ELL blocks
        with span("build.place"):
            self._blocks = tuple(
                shard_ranks(a.reshape(g, L, -1), self.mesh)
                for a in (part.diag.data, part.diag.cols, part.off.data, part.off.cols)
            )
        # per-instance memo over the module LRU: matmat's hot path must not
        # re-derive the (fingerprint, k, mesh) key per call
        self._mm_programs: dict = {}

        self._split: Optional[RowPhaseSplit] = None
        if self.overlap:
            self._masks = phase_masks(self.row_split, L, self.mesh)
            self._phase_fn = _phase_program(
                self._fingerprint, self.mesh, self.use_pallas, None
            )
            self._mm_phase_programs: dict = {}

    @property
    def row_split(self) -> RowPhaseSplit:
        """Interior/boundary row split at the kernels' row tile (lazily built);
        see :func:`row_split`."""
        if self._split is None:
            self._split = row_split(self.partition)
        return self._split

    # ------------------------------------------------------------------
    def __call__(self, v: jax.Array) -> jax.Array:
        """``v [nranks, L] -> w [nranks, L]``; a trailing feature dim
        (``[nranks, L, k]``) dispatches to :meth:`matmat`."""
        if v.ndim == 3:
            return self.matmat(v)
        with span("spmv"):
            if not self.overlap:
                halo = self.exchange(v)
                return self._compute(v, halo, *self._blocks)
            all_tiles, all_rows, bnd_tiles, bnd_rows = self._masks
            handle = self.exchange.start(v)
            # the whole halo-independent diag block runs while the inter-pod
            # phase is in flight; only boundary tiles' off-block waits on it
            w_diag = self._phase_fn(v, *self._blocks[:2], all_tiles, all_rows)
            halo = handle.finish()
            w_off = self._phase_fn(halo, *self._blocks[2:], bnd_tiles, bnd_rows)
            return w_diag + w_off

    def matmat(self, V: jax.Array) -> jax.Array:
        """``V [nranks, L, k] -> W [nranks, L, k]`` under ONE exchange.

        All ``k`` columns ride the single cached plan
        (:meth:`repro.comm.strategies.IrregularExchange.__call__`) and the
        local compute is one fused blocked-ELL SpMM per block -- no Python
        loop over columns.  The compiled program is cached per
        ``(pattern fingerprint, k)``.  With ``overlap=True`` the exchange is
        split-phase and the diag-block SpMM computes during the inter-node
        phase.
        """
        if V.ndim != 3:
            raise ValueError(f"matmat expects [nranks, L, k], got {tuple(V.shape)}")
        k = int(V.shape[2])
        with span("spmv"):
            if not self.overlap:
                halo = self.exchange(V)
                fn = self._mm_programs.get(k)
                if fn is None:
                    fn = self._mm_programs[k] = _compute_program(
                        self._fingerprint, self.mesh, self.use_pallas, k
                    )
                return fn(V, halo, *self._blocks)
            fn = self._mm_phase_programs.get(k)
            if fn is None:
                fn = self._mm_phase_programs[k] = _phase_program(
                    self._fingerprint, self.mesh, self.use_pallas, k
                )
            all_tiles, all_rows, bnd_tiles, bnd_rows = self._masks
            handle = self.exchange.start(V)
            w_diag = fn(V, *self._blocks[:2], all_tiles, all_rows)
            halo = handle.finish()
            w_off = fn(halo, *self._blocks[2:], bnd_tiles, bnd_rows)
            return w_diag + w_off

    def matmat_looped(self, V: jax.Array) -> jax.Array:
        """Per-column baseline: ``k`` exchanges + ``k`` local SpMVs.

        Kept as the comparison path for benchmarks/tests; :meth:`matmat` is
        the serving path.
        """
        if V.ndim != 3:
            raise ValueError(f"matmat_looped expects [nranks, L, k], got {tuple(V.shape)}")
        cols = [self(V[:, :, c]) for c in range(V.shape[2])]
        return jnp.stack(cols, axis=-1)

    def halo(self, v: jax.Array) -> jax.Array:
        """Exchange-only entry point.

        Accepts batched payloads ``[nranks, L, k]`` (multi-vector SpMM /
        batched serving) under the same plan; see
        :meth:`repro.comm.strategies.IrregularExchange.__call__`.
        """
        return self.exchange(v)

    # ------------------------------------------------------------------
    @property
    def topo(self) -> PodTopology:
        """The pod topology (the solver-facing operator contract shared
        with :class:`repro.solve.operator.NumpySpMV`)."""
        return self.partition.topo

    @property
    def rows_per_rank(self) -> int:
        return self.partition.rows_per_rank

    @property
    def wire_bytes(self) -> Tuple[int, int]:
        return self.exchange.wire_bytes


def build(
    matrix: CSRMatrix,
    topo: PodTopology,
    strategy: str = "auto",
    **kw,
) -> DistributedSpMV:
    return DistributedSpMV(partition_csr(matrix, topo), strategy=strategy, **kw)


def reference(matrix: CSRMatrix, v_flat: np.ndarray) -> np.ndarray:
    """Sequential oracle on the unpartitioned matrix."""
    return matrix.spmv(v_flat)


def reference_mm(matrix: CSRMatrix, V_flat: np.ndarray) -> np.ndarray:
    """Sequential multi-vector oracle on the unpartitioned matrix."""
    return matrix.spmm(V_flat)
