"""The benchmark's own CSR matrix and its float64 reference products.

A copy, kept with the benchmark, of the COO-to-CSR assembly that the
program's generators use (``repro.sparse.matrices._from_coo``), so that no
change to the program can change the data a cell runs on.  The reference
products here are vectorised numpy in float64 on the unpartitioned matrix;
they import nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Csr:
    n: int
    indptr: np.ndarray  # [n + 1] int64
    indices: np.ndarray  # [nnz] int32, sorted and unique within each row
    data: np.ndarray  # [nnz] float32

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def row_ids(self) -> np.ndarray:
        """The row of every stored entry, ``[nnz]`` int64."""
        return np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))


def from_coo(n: int, rows, cols, vals, duplicates: str = "first") -> Csr:
    """COO triplets to CSR, rows lexsorted and columns sorted within a row.

    ``duplicates="first"`` keeps the earliest of repeated ``(row, col)``
    entries in input order; ``"sum"`` adds them in float64.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    key = rows * n + cols
    keep = np.ones(key.shape, dtype=bool)
    keep[1:] = key[1:] != key[:-1]
    if duplicates == "sum":
        group = np.cumsum(keep) - 1
        summed = np.zeros(int(keep.sum()), dtype=np.float64)
        np.add.at(summed, group, vals.astype(np.float64))
        vals = summed
    elif duplicates == "first":
        vals = vals[keep]
    else:
        raise ValueError(f"duplicates must be 'first' or 'sum', got {duplicates!r}")
    rows, cols = rows[keep], cols[keep]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return Csr(
        n=n,
        indptr=np.cumsum(indptr),
        indices=cols.astype(np.int32),
        data=vals.astype(np.float32),
    )


def spmv_f64(A: Csr, x: np.ndarray) -> np.ndarray:
    """``A @ x`` in float64, vectorised over the stored entries."""
    prod = A.data.astype(np.float64) * np.asarray(x, np.float64)[A.indices]
    return np.bincount(A.row_ids(), weights=prod, minlength=A.n)
