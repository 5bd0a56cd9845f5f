"""The comparisons that decide ``correct``, against the float64 reference.

Each returns one number per answer; the harness holds the largest against
the cell's limit (``bench/limits/<workload>.json``).  The reference is the
benchmark's own CSR product on the unpartitioned matrix (:mod:`bench.csr`).
"""

from __future__ import annotations

import numpy as np

from bench.csr import Csr, spmv_f64


def true_residual(A: Csr, b: np.ndarray, x: np.ndarray) -> float:
    """``||b - A x|| / ||b||`` in float64: a solve's answer judged by what it
    says, whatever the solver's own residual recursion claimed."""
    b = np.asarray(b, np.float64).reshape(-1)
    r = b - spmv_f64(A, np.asarray(x).reshape(-1))
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def product_error(want: np.ndarray, got: np.ndarray) -> float:
    """``max |got - want| / max |want|``: one wrong row shows, whichever."""
    want = np.asarray(want, np.float64).reshape(-1)
    got = np.asarray(got, np.float64).reshape(-1)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
