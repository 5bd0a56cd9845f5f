"""The least work a product or a CG iteration needs, counted from the matrix.

These counts are lower bounds on any implementation, not counts of what
today's kernels move: a value of the matrix is read once (4 bytes), a vector
element is read or written once (4 bytes), and no index array is counted,
because a stencil kernel needs none.  So a later kernel that drops the
gather or the indices is read against the same floor, and a share of it can
never pass 100 % while the time covers the work.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.csr import Csr

F32 = 4  # bytes of one stored value or vector element

#: vector passes of one CG iteration with every vector operation fused:
#: x, r and p are each read once and written once
CG_VECTOR_PASSES = 6


@dataclasses.dataclass(frozen=True)
class RankWork:
    """One rank's share of a row-partitioned product."""

    rows: int  # L, the rank's slice of x and of w
    nnz: int  # stored entries in the rank's rows
    halo: int  # distinct columns the rank reads from other ranks

    @property
    def bytes(self) -> int:
        # values + own x slice + halo read + w slice written
        return F32 * (self.nnz + self.rows + self.halo + self.rows)

    @property
    def flops(self) -> int:
        return 2 * self.nnz


def rank_work(A: Csr, nranks: int) -> list[RankWork]:
    """Per-rank rows, nonzeros and halo width under contiguous row blocks."""
    if A.n % nranks:
        raise ValueError(f"{A.n} rows do not split over {nranks} ranks")
    L = A.n // nranks
    out = []
    for r in range(nranks):
        lo, hi = A.indptr[r * L], A.indptr[(r + 1) * L]
        cols = A.indices[lo:hi]
        remote = cols[(cols < r * L) | (cols >= (r + 1) * L)]
        out.append(RankWork(rows=L, nnz=int(hi - lo), halo=int(np.unique(remote).size)))
    return out


def floor_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The larger of the compute and the memory time, and which one binds."""
    t_flops = flops / peaks.flops_per_s
    t_bytes = nbytes / peaks.hbm_bytes_per_s
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")


def cg_iteration_work(n: int, nnz: int, halo: int = 0) -> tuple[int, int]:
    """(flops, bytes) of one textbook CG iteration on ``n`` rows with ``nnz``
    entries (a whole matrix, or one rank's rows reading ``halo`` values of
    ``p`` from other ranks): one product, two dot products and three vector
    updates, every vector pass fused."""
    flops = 2 * nnz + 2 * 2 * n + 3 * 2 * n
    nbytes = F32 * (nnz + halo + CG_VECTOR_PASSES * n)
    return flops, nbytes
