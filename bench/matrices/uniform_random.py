"""Uniform random sparsity plus the diagonal: every rank needs every other.

A copy of ``repro.sparse.matrices.random_block``, kept with the benchmark.
The sparsity comes from the configuration's fixed ``structure_seed`` (the
deployment's graph), the values from the run's seed, so every seed runs
the same shapes: the same ELL widths, halo and exchange plan.
"""

from __future__ import annotations

import numpy as np

from bench.csr import Csr, from_coo


def random_block(n: int, density: float, structure: np.random.Generator,
                 values: np.random.Generator) -> Csr:
    """``max(n, n * n * density)`` uniform ``(row, col)`` draws plus the
    diagonal; a repeated entry keeps its first draw.  Passing one generator
    as both arguments reproduces ``random_block(n, density, rng)``."""
    nnz = max(n, int(n * n * density))
    rows = structure.integers(0, n, size=nnz)
    cols = structure.integers(0, n, size=nnz)
    diag = np.arange(n)
    rows = np.concatenate([rows, diag])
    cols = np.concatenate([cols, diag])
    vals = values.normal(size=rows.size)
    return from_coo(n, rows, cols, vals)


def generate(spec: dict, seed: int) -> Csr:
    """The configuration's matrix: ``2 ** scale`` rows, ``edgefactor``
    random entries a row on average, structure from ``structure_seed``."""
    n = 2 ** int(spec["scale"])
    density = float(spec["edgefactor"]) / n
    return random_block(n, density, np.random.default_rng(int(spec["structure_seed"])),
                        np.random.default_rng(seed))
