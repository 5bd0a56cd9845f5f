"""The benchmark's copied generators give the program's CSR, and its float64
reference gives the program's sequential product."""

import numpy as np
import pytest

from bench import check
from bench.csr import spmv_f64
from bench.matrices import stencil5_spd, uniform_random
from repro.solve import spd_system
from repro.sparse import random_block, thermal_like


def _same(bench_csr, repro_csr):
    assert bench_csr.n == repro_csr.n
    np.testing.assert_array_equal(bench_csr.indptr, repro_csr.indptr)
    np.testing.assert_array_equal(bench_csr.indices, repro_csr.indices)
    np.testing.assert_array_equal(bench_csr.data, repro_csr.data)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_stencil_matches_program(seed):
    ours = stencil5_spd.generate({"grid_side": 32, "spd_shift": 1.0}, seed)
    theirs = spd_system(thermal_like(32 * 32, np.random.default_rng(seed)), 1.0)
    _same(ours, theirs)


def test_random_block_matches_program():
    rng = np.random.default_rng(3)
    _same(uniform_random.random_block(512, 16 / 512, rng, rng),
          random_block(512, 16 / 512, np.random.default_rng(3)))


def test_structure_fixed_values_from_seed():
    spec = {"scale": 9, "edgefactor": 16, "structure_seed": 0}
    a, b = uniform_random.generate(spec, 1), uniform_random.generate(spec, 2)
    program = random_block(512, 16 / 512, np.random.default_rng(0))
    for m in (a, b):
        np.testing.assert_array_equal(m.indptr, program.indptr)
        np.testing.assert_array_equal(m.indices, program.indices)
    assert not np.array_equal(a.data, b.data)


def test_reference_matches_program_product():
    from repro.sparse.matrices import CSRMatrix

    A = uniform_random.generate({"scale": 8, "edgefactor": 16, "structure_seed": 0}, 7)
    x = np.random.default_rng(1).standard_normal(A.n)
    prog = CSRMatrix(n=A.n, indptr=A.indptr, indices=A.indices, data=A.data)
    np.testing.assert_allclose(spmv_f64(A, x), prog.spmv(x), rtol=1e-12, atol=1e-12)


def test_checks_read_wrong_answers():
    A = stencil5_spd.generate({"grid_side": 8, "spd_shift": 1.0}, 0)
    b = np.random.default_rng(0).standard_normal(A.n)
    x = np.linalg.solve(_dense(A), b)
    assert check.true_residual(A, b, x) < 1e-12
    assert check.true_residual(A, b, np.zeros_like(b)) == pytest.approx(1.0)
    w = spmv_f64(A, b)
    bad = w.copy()
    bad[3] += 1.0
    assert check.product_error(w, w) == 0.0
    assert check.product_error(w, bad) == pytest.approx(1.0 / np.abs(w).max())
    assert not check.product_error(w, np.full_like(w, np.nan)) <= 1.0


def _dense(A):
    D = np.zeros((A.n, A.n))
    D[A.row_ids(), A.indices] = A.data
    return D
