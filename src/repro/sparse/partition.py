"""Row-wise partitioning of a sparse matrix for distributed SpMV (paper §2.4.1).

``A``, ``v``, ``w`` are partitioned row-wise across ``g`` ranks with
contiguous rows per rank.  Each rank's rows split into the **on-rank block**
(columns it owns) and the **off-rank block** (columns owned elsewhere); the
off-rank column set induces the irregular point-to-point pattern
(:class:`repro.comm.exchange.ExchangePattern`) the paper studies.

Local storage is blocked-ELL (rows x max_nnz_per_row), the TPU-friendly
layout consumed by :mod:`repro.kernels.spmv_ell`: column ids of the off-rank
block are rewritten to positions in the canonical halo buffer produced by the
exchange.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

from repro.comm.exchange import ExchangePattern, Need
from repro.comm.topology import PodTopology
from repro.sparse.matrices import CSRMatrix
from repro.trace import span


@dataclasses.dataclass(frozen=True)
class EllBlock:
    """Padded ELL block: ``w[i] += sum_k data[i,k] * x[cols[i,k]]``.

    Padding entries have ``data == 0`` and ``cols == 0``.
    """

    data: np.ndarray  # [rows, K] float32
    cols: np.ndarray  # [rows, K] int32


@dataclasses.dataclass(frozen=True)
class SpmvPartition:
    """Everything each rank needs, stacked over ranks (leading dim nranks)."""

    topo: PodTopology
    rows_per_rank: int
    pattern: ExchangePattern
    # stacked blocked-ELL storage, one slice per rank:
    diag: EllBlock  # cols index into the rank's own v slice [0, L)
    off: EllBlock  # cols index into the canonical halo buffer [0, H)
    halo_width: int
    #: structural off-rank nonzeros per row ``[nranks * L]`` -- the
    #: interior/boundary classifier for split-phase compute (a row with 0
    #: has a pure-padding off-ELL row, including explicitly stored zeros)
    off_row_nnz: np.ndarray

    @property
    def n(self) -> int:
        return self.topo.nranks * self.rows_per_rank


def partition_csr(matrix: CSRMatrix, topo: PodTopology) -> SpmvPartition:
    """Partition ``matrix`` row-wise over ``topo.nranks`` ranks.

    Host span ``repro.partition``, with one child span per host loop:
    ``.needs``, ``.pattern``, ``.widths``, ``.fill``.
    """
    with span("partition"):
        return _partition_csr(matrix, topo)


def _partition_csr(matrix: CSRMatrix, topo: PodTopology) -> SpmvPartition:
    g = topo.nranks
    if matrix.n % g:
        raise ValueError(f"matrix dim {matrix.n} not divisible by {g} ranks")
    L = matrix.n // g

    def owner(col: int) -> int:
        return col // L

    # 1. per-rank column dependencies
    with span("partition.needs"):
        needs_by_pair: Dict[Tuple[int, int], set] = defaultdict(set)
        for r in range(g):
            for i in range(r * L, (r + 1) * L):
                cols, _ = matrix.row(i)
                for c in cols:
                    o = owner(int(c))
                    if o != r:
                        needs_by_pair[(r, o)].add(int(c) - o * L)
        needs = tuple(
            Need(dst=dst, src=src, idx=tuple(sorted(elems)))
            for (dst, src), elems in sorted(needs_by_pair.items())
        )

    # 2. exchange pattern and canonical halo layout: position of (owner,
    # elem) in dst's recv buffer
    with span("partition.pattern"):
        pattern = ExchangePattern(topo=topo, local_size=L, needs=needs)
        halo_pos: List[Dict[Tuple[int, int], int]] = []
        for r in range(g):
            pos = {tok: k for k, tok in enumerate(pattern.canonical_tokens(r))}
            halo_pos.append(pos)
        H = max(pattern.max_recv_size(), 1)

    # 3. ELL widths of the diag and off blocks
    with span("partition.widths"):
        kd = ko = 1
        for r in range(g):
            for i in range(r * L, (r + 1) * L):
                cols, _ = matrix.row(i)
                on = sum(owner(int(c)) == r for c in cols)
                kd = max(kd, on)
                ko = max(ko, len(cols) - on)

    # 4. per-rank ELL blocks with rewritten column ids
    with span("partition.fill"):
        diag_data = np.zeros((g, L, kd), dtype=np.float32)
        diag_cols = np.zeros((g, L, kd), dtype=np.int32)
        off_data = np.zeros((g, L, ko), dtype=np.float32)
        off_cols = np.zeros((g, L, ko), dtype=np.int32)
        off_row_nnz = np.zeros(g * L, dtype=np.int64)
        for r in range(g):
            for li in range(L):
                cols, vals = matrix.row(r * L + li)
                di = oi = 0
                for c, vv in zip(cols, vals):
                    o = owner(int(c))
                    if o == r:
                        diag_data[r, li, di] = vv
                        diag_cols[r, li, di] = int(c) - r * L
                        di += 1
                    else:
                        off_data[r, li, oi] = vv
                        off_cols[r, li, oi] = halo_pos[r][(o, int(c) - o * L)]
                        oi += 1
                off_row_nnz[r * L + li] = oi

    return SpmvPartition(
        topo=topo,
        rows_per_rank=L,
        pattern=pattern,
        diag=EllBlock(data=diag_data.reshape(g * L, kd), cols=diag_cols.reshape(g * L, kd)),
        off=EllBlock(data=off_data.reshape(g * L, ko), cols=off_cols.reshape(g * L, ko)),
        halo_width=H,
        off_row_nnz=off_row_nnz,
    )
