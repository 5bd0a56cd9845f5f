"""CSRMatrix structural-invariant tests.

The dataclass documents "indices sorted per row"; downstream code
(partition canonical orders, the ELL rewrite) silently relies on it, so
``CSRMatrix.validate`` now enforces it and every generator is
property-tested against it.  All generators funnel through ``_from_coo``
(lexsort by (row, col) + dedup), which is what establishes the invariant;
a generator bypassing it would be caught here.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.sparse.matrices import GENERATORS, CSRMatrix, banded, _from_coo


@given(
    seed=st.integers(0, 1000),
    name=st.sampled_from(sorted(GENERATORS)),
    n=st.sampled_from([32, 64, 128, 144]),
)
@settings(max_examples=30, deadline=None)
def test_generators_satisfy_csr_invariants(seed, name, n):
    A = GENERATORS[name](n, np.random.default_rng(seed))
    assert A.validate() is A
    # per-row view agrees: sorted strictly (no duplicate columns)
    for i in range(A.n):
        cols, _ = A.row(i)
        assert (np.diff(cols) > 0).all(), (name, i)


@given(seed=st.integers(0, 200), bw=st.integers(1, 9))
@settings(max_examples=15, deadline=None)
def test_banded_satisfies_csr_invariants(seed, bw):
    banded(48, bw, np.random.default_rng(seed)).validate()


def test_from_coo_sorts_and_dedups_unsorted_input():
    rows = np.array([1, 0, 1, 1, 0])
    cols = np.array([2, 1, 0, 2, 1])  # row 1 unsorted + dup (1,2); dup (0,1)
    vals = np.arange(5, dtype=np.float64)
    A = _from_coo(3, rows, cols, vals)
    A.validate()
    np.testing.assert_array_equal(A.indices, [1, 0, 2])
    np.testing.assert_array_equal(A.indptr, [0, 1, 3, 3])
    # dedup keeps the first occurrence in the original order
    np.testing.assert_array_equal(A.data, [1.0, 2.0, 0.0])


def test_from_coo_sums_duplicates_and_accepts_empty():
    rows = np.array([0, 0, 1])
    cols = np.array([1, 1, 0])
    vals = np.array([2.0, 3.0, 4.0])
    A = _from_coo(2, rows, cols, vals, duplicates="sum")
    np.testing.assert_array_equal(A.to_dense(), [[0.0, 5.0], [4.0, 0.0]])
    with pytest.raises(ValueError, match="duplicates"):
        _from_coo(2, rows, cols, vals, duplicates="max")
    # empty COO input is a valid all-empty matrix, not a crash
    for dup in ("first", "sum"):
        E = _from_coo(3, np.array([], np.int64), np.array([], np.int64),
                      np.array([], np.float64), duplicates=dup)
        assert E.validate().nnz == 0
        np.testing.assert_array_equal(E.indptr, [0, 0, 0, 0])


def test_solve_problems_on_diagonal_only_matrix():
    """spd_system/shifted_system must survive a matrix with no off-diagonal
    entries (the empty-COO edge of the symmetrization path)."""
    from repro.solve import shifted_system, spd_system

    n = 4
    D = CSRMatrix(
        n=n,
        indptr=np.arange(n + 1, dtype=np.int64),
        indices=np.arange(n, dtype=np.int32),
        data=np.full(n, 2.0, np.float32),
    )
    S = spd_system(D)
    np.testing.assert_array_equal(S.to_dense(), np.eye(n, dtype=np.float32))
    T = shifted_system(D)
    np.testing.assert_array_equal(T.to_dense(), 0.5 * np.eye(n, dtype=np.float32))


def test_validate_rejects_malformed():
    ok = GENERATORS["thermal_like"](64, np.random.default_rng(0))
    # unsorted indices within a row
    bad = ok.indices.copy()
    s, e = ok.indptr[1], ok.indptr[2]
    assert e - s >= 2
    bad[s], bad[s + 1] = bad[s + 1], bad[s]
    with pytest.raises(ValueError, match="not strictly sorted within row 1"):
        CSRMatrix(ok.n, ok.indptr, bad, ok.data).validate()
    # duplicate column in a row
    dup = ok.indices.copy()
    dup[s + 1] = dup[s]
    with pytest.raises(ValueError, match="not strictly sorted"):
        CSRMatrix(ok.n, ok.indptr, dup, ok.data).validate()
    # column id out of range
    oob = ok.indices.copy()
    oob[0] = ok.n
    with pytest.raises(ValueError, match="out of range"):
        CSRMatrix(ok.n, ok.indptr, oob, ok.data).validate()
    # indptr not monotone
    ptr = ok.indptr.copy()
    ptr[1], ptr[2] = ptr[2], ptr[1]
    with pytest.raises(ValueError, match="non-decreasing"):
        CSRMatrix(ok.n, ptr, ok.indices, ok.data).validate()
    # length mismatch
    with pytest.raises(ValueError, match="length"):
        CSRMatrix(ok.n, ok.indptr, ok.indices[:-1], ok.data[:-1]).validate()
    # indptr shape
    with pytest.raises(ValueError, match="indptr shape"):
        CSRMatrix(ok.n + 1, ok.indptr, ok.indices, ok.data).validate()


def test_validate_accepts_empty_rows():
    # row 0 and row 2 empty: indptr repeats, boundary mask must not wrap
    A = CSRMatrix(
        n=3,
        indptr=np.array([0, 0, 2, 2]),
        indices=np.array([0, 2], np.int32),
        data=np.ones(2, np.float32),
    )
    assert A.validate() is A
