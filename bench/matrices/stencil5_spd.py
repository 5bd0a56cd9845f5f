"""The 2-D 5-point stencil made SPD: a weighted grid Laplacian plus ``shift * I``.

Copies of ``repro.sparse.matrices.thermal_like`` and
``repro.solve.problems.spd_system``, kept with the benchmark so that the
data of a cell cannot change with the program.  The grid is fixed by the
configuration; the edge weights are drawn from the run's seed.
"""

from __future__ import annotations

import numpy as np

from bench.csr import Csr, from_coo


def thermal_like(n: int, rng: np.random.Generator) -> Csr:
    """2-D 5-point stencil on a ``sqrt(n) x sqrt(n)`` grid, normal weights."""
    side = int(np.floor(np.sqrt(n)))
    n = side * side
    idx = np.arange(n)
    x, y = idx % side, idx // side
    rows_l, cols_l = [idx], [idx]
    for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
        nx, ny = x + dx, y + dy
        ok = (0 <= nx) & (nx < side) & (0 <= ny) & (ny < side)
        rows_l.append(idx[ok])
        cols_l.append((ny * side + nx)[ok])
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    vals = rng.normal(size=rows.size)
    return from_coo(n, rows, cols, vals)


def spd_system(A: Csr, shift: float = 1.0) -> Csr:
    """Off-diagonals ``-(|a_ij| + |a_ji|) / 2``, diagonal ``shift`` plus the
    row's off-diagonal magnitudes: symmetric, strictly diagonally dominant."""
    if shift <= 0:
        raise ValueError(f"shift must be > 0, got {shift}")
    rows, cols, vals = A.row_ids(), A.indices.astype(np.int64), A.data.astype(np.float64)
    r2 = np.concatenate([rows, cols])
    c2 = np.concatenate([cols, rows])
    v2 = np.concatenate([np.abs(vals), np.abs(vals)]) * 0.5
    off = r2 != c2
    W = from_coo(A.n, r2[off], c2[off], v2[off], duplicates="sum")
    wrows = W.row_ids()
    degree = np.zeros(A.n, dtype=np.float64)
    np.add.at(degree, wrows, W.data.astype(np.float64))
    rows3 = np.concatenate([wrows, np.arange(A.n)])
    cols3 = np.concatenate([W.indices.astype(np.int64), np.arange(A.n)])
    vals3 = np.concatenate([-W.data.astype(np.float64), shift + degree])
    return from_coo(A.n, rows3, cols3, vals3, duplicates="sum")


def generate(spec: dict, seed: int) -> Csr:
    """The configuration's matrix, from its ``grid_side`` and ``spd_shift``."""
    side = int(spec["grid_side"])
    rng = np.random.default_rng(seed)
    return spd_system(thermal_like(side * side, rng), float(spec["spd_shift"]))
