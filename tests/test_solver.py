"""Krylov solver correctness over the node-aware exchange.

Three layers of guarantees:

* **Algebra** -- CG / BiCGStab on the jax-free numpy executor converge to
  the ``np.linalg.solve`` reference on all three matrix regimes
  (property-tested over seeds / regimes / strategies).
* **Executor equivalence** -- residual histories are *bitwise identical*
  across every strategy and across barrier-vs-split-phase execution on the
  numpy executor (every strategy delivers the same canonical halo buffer,
  so the whole solve trajectory must agree bit for bit), and on 8 devices
  with the Pallas kernels (slow subprocess test).
* **Amortization plumbing** -- one solve incurs exactly ONE exchange-plan
  miss (the property ``advise_solver`` prices), visible via
  ``repro.comm.cache_stats()``.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import strategies as comm_strategies
from repro.comm.topology import PodTopology
from repro.sparse import partition_csr, thermal_like
from repro.sparse.matrices import GENERATORS
from repro.solve import (
    NumpyReductions,
    NumpySpMV,
    bicgstab,
    build_numpy,
    cg,
    shifted_system,
    spd_system,
)

ALL_STRATEGIES = ("standard", "two_step", "three_step", "split")
TOPO = PodTopology(npods=2, ppn=4)


def _rhs(part, rng, dtype=np.float64):
    return rng.normal(size=(TOPO.nranks, part.rows_per_rank)).astype(dtype)


def _dense_solve(A, b):
    return np.linalg.solve(A.to_dense().astype(np.float64), b.reshape(-1))


# ---------------------------------------------------------------------------
# Algebra: solvers vs the dense numpy reference, all three regimes
# ---------------------------------------------------------------------------


@pytest.mark.slow
@given(
    seed=st.integers(0, 500),
    regime=st.sampled_from(sorted(GENERATORS)),
    strategy=st.sampled_from(list(ALL_STRATEGIES)),
    overlap=st.sampled_from([False, True]),
)
@settings(max_examples=12, deadline=None)
def test_cg_matches_dense_reference(seed, regime, strategy, overlap):
    rng = np.random.default_rng(seed)
    A = spd_system(GENERATORS[regime](144, rng))
    part = partition_csr(A, TOPO)
    op = NumpySpMV(part, strategy=strategy, overlap=overlap)
    b = _rhs(part, rng)
    res = cg(op, b, tol=1e-10, maxiter=2000)
    assert res.converged, (regime, strategy, res.final_residual)
    want = _dense_solve(A, b)
    np.testing.assert_allclose(res.x.reshape(-1), want, rtol=1e-6, atol=1e-7)
    # the recursive residual history is honest: recompute the true residual
    r_true = b - np.asarray(op(res.x))
    bnorm = np.linalg.norm(b.reshape(-1))
    assert np.linalg.norm(r_true.reshape(-1)) / bnorm < 1e-8


@pytest.mark.slow
@given(
    seed=st.integers(0, 500),
    regime=st.sampled_from(sorted(GENERATORS)),
    strategy=st.sampled_from(list(ALL_STRATEGIES)),
)
@settings(max_examples=9, deadline=None)
def test_bicgstab_matches_dense_reference(seed, regime, strategy):
    rng = np.random.default_rng(seed)
    A = shifted_system(GENERATORS[regime](144, rng))
    part = partition_csr(A, TOPO)
    op = NumpySpMV(part, strategy=strategy)
    b = _rhs(part, rng)
    res = bicgstab(op, b, tol=1e-10, maxiter=2000)
    assert res.converged, (regime, strategy, res.final_residual)
    want = _dense_solve(A, b)
    np.testing.assert_allclose(res.x.reshape(-1), want, rtol=1e-6, atol=1e-7)


def test_cg_spd_problem_is_required():
    """On a raw random-valued (indefinite) matrix CG must fail safely: the
    pAp<=0 breakdown guard trips instead of NaN-ing the iterate."""
    rng = np.random.default_rng(3)
    A = GENERATORS["thermal_like"](256, rng)  # random values: not SPD
    part = partition_csr(A, TOPO)
    res = cg(NumpySpMV(part), _rhs(part, rng), tol=1e-10, maxiter=50)
    assert not res.converged
    assert np.isfinite(res.x).all()


# ---------------------------------------------------------------------------
# Executor equivalence: bitwise-identical histories (acceptance criterion)
# ---------------------------------------------------------------------------


def test_cg_histories_bitwise_identical_across_strategies_and_overlap():
    """repro.solve.cg on thermal_like converges to 1e-6 relative residual
    with IDENTICAL iteration counts -- and in fact bitwise-identical
    residual histories and iterates -- across all strategies and overlap
    on/off on the numpy executor."""
    rng = np.random.default_rng(0)
    A = spd_system(thermal_like(256, rng))
    part = partition_csr(A, TOPO)
    b = _rhs(part, rng)
    results = {}
    for strategy in ALL_STRATEGIES:
        for overlap in (False, True):
            op = NumpySpMV(part, strategy=strategy, overlap=overlap)
            results[(strategy, overlap)] = cg(op, b, tol=1e-6)
    ref = results[("standard", False)]
    assert ref.converged and ref.final_residual <= 1e-6
    assert ref.iterations > 5
    assert len(ref.residuals) == ref.iterations + 1
    for key, res in results.items():
        assert res.converged, key
        assert res.iterations == ref.iterations, key
        assert res.residuals == ref.residuals, f"history drift for {key}"
        np.testing.assert_array_equal(res.x, ref.x, err_msg=str(key))


def test_bicgstab_histories_bitwise_identical_across_strategies():
    rng = np.random.default_rng(7)
    A = shifted_system(GENERATORS["random_block"](144, rng))
    part = partition_csr(A, TOPO)
    b = _rhs(part, rng)
    results = [
        bicgstab(NumpySpMV(part, strategy=s, overlap=ov), b, tol=1e-8)
        for s in ALL_STRATEGIES
        for ov in (False, True)
    ]
    ref = results[0]
    assert ref.converged
    for res in results[1:]:
        assert res.residuals == ref.residuals
        np.testing.assert_array_equal(res.x, ref.x)


# ---------------------------------------------------------------------------
# Amortization plumbing: ONE plan per solve
# ---------------------------------------------------------------------------


def test_full_solve_incurs_exactly_one_plan_miss():
    """The whole point of the solver workload: every iteration reuses the
    single cached exchange plan, so a full solve = exactly one plan miss."""
    rng = np.random.default_rng(1)
    A = spd_system(thermal_like(256, rng))
    part = partition_csr(A, TOPO)
    b = _rhs(part, rng)
    comm_strategies.clear_caches()
    op = NumpySpMV(part, strategy="two_step")
    res = cg(op, b, tol=1e-6)
    stats = comm_strategies.cache_stats()
    assert res.converged and res.matvecs > 5
    assert stats.plan_misses == 1, stats
    assert stats.plan_hits == 0, stats
    assert stats.split_misses == 0, stats
    # a second solve on a rebuilt operator re-plans nothing at all
    op2 = NumpySpMV(part, strategy="two_step")
    cg(op2, b, tol=1e-6)
    stats = comm_strategies.cache_stats()
    assert stats.plan_misses == 1 and stats.plan_hits == 1, stats
    comm_strategies.clear_caches()


def test_overlapped_solve_plans_both_phases_once():
    rng = np.random.default_rng(1)
    A = spd_system(thermal_like(256, rng))
    part = partition_csr(A, TOPO)
    b = _rhs(part, rng)
    comm_strategies.clear_caches()
    op = NumpySpMV(part, strategy="split", overlap=True)
    res = cg(op, b, tol=1e-6)
    stats = comm_strategies.cache_stats()
    assert res.converged
    # one split-phase decomposition + one plan per phase, zero re-plans
    assert stats.split_misses == 1 and stats.split_hits == 0, stats
    assert stats.plan_misses == 2 and stats.plan_hits == 0, stats
    comm_strategies.clear_caches()


# ---------------------------------------------------------------------------
# API edges
# ---------------------------------------------------------------------------


def test_solver_edge_cases():
    rng = np.random.default_rng(5)
    A = spd_system(thermal_like(64, rng))
    op = build_numpy(A, TOPO, strategy="two_step")
    L = op.rows_per_rank
    # zero rhs: trivially converged, no matvecs
    res = cg(op, np.zeros((TOPO.nranks, L)))
    assert res.converged and res.iterations == 0 and res.matvecs == 0
    assert res.residuals == (0.0,)
    # warm start from the exact solution: converged before iterating
    b = _rhs(op.partition, rng)
    exact = cg(op, b, tol=1e-12, maxiter=2000)
    warm = cg(op, b, x0=exact.x, tol=1e-6)
    assert warm.converged and warm.iterations == 0 and warm.matvecs == 1
    # maxiter exhaustion reports non-convergence with full history
    hard = cg(op, b, tol=1e-16, maxiter=3)
    assert not hard.converged and hard.iterations == 3
    assert len(hard.residuals) == 4
    # shape validation
    with pytest.raises(ValueError, match="b must be"):
        cg(op, np.zeros((TOPO.nranks, L + 1)))
    with pytest.raises(ValueError, match="expected"):
        op(np.zeros((TOPO.nranks, L + 1)))
    with pytest.raises(ValueError, match="unknown strategy"):
        NumpySpMV(op.partition, strategy="bogus")


def test_early_returns_route_through_finish_status(monkeypatch):
    """Regression: the zero-rhs and warm-start exits must report through
    ``_finish_status`` like every other exit path -- the recovery-suffix
    contract (``+exchange:<strategy>`` when the operator healed mid-solve)
    holds for trivial solves too.  Pins every early-return path for both
    solvers and proves the routing by counting ``_finish_status`` calls."""
    import repro.solve.krylov as K

    rng = np.random.default_rng(5)
    A = spd_system(thermal_like(64, rng))
    op = build_numpy(A, TOPO, strategy="two_step")
    L = op.rows_per_rank
    z = np.zeros((TOPO.nranks, L))
    b = _rhs(op.partition, rng)

    calls = []
    orig = K._finish_status

    def spy(status, restarts, op_, rc0):
        calls.append(status)
        return orig(status, restarts, op_, rc0)

    monkeypatch.setattr(K, "_finish_status", spy)
    for solver in (cg, bicgstab):
        # zero rhs: trivially converged, no matvecs, clean status
        calls.clear()
        r = solver(op, z)
        assert calls == ["converged"], f"{solver.__name__} bypassed _finish_status"
        assert r.status == "converged" and r.restarts == 0
        assert r.converged and r.iterations == 0 and r.matvecs == 0
        assert r.residuals == (0.0,)
        # warm start from the exact solution: one true-residual matvec, no
        # iterations, same routing
        exact = solver(op, b, tol=1e-10, maxiter=2000)
        calls.clear()
        warm = solver(op, b, x0=exact.x, tol=1e-6)
        assert calls == ["converged"]
        assert warm.status == "converged" and warm.restarts == 0
        assert warm.converged and warm.iterations == 0 and warm.matvecs == 1
        assert len(warm.residuals) == 1


def test_numpy_reductions_hierarchical_order():
    red = NumpyReductions(TOPO)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(TOPO.nranks, 17))
    y = rng.normal(size=(TOPO.nranks, 17))
    assert red.dot(x, y) == pytest.approx(float(x.reshape(-1) @ y.reshape(-1)))
    assert red.norm(x) == pytest.approx(float(np.linalg.norm(x)))
    # deterministic: bitwise-stable across calls
    assert red.dot(x, y) == red.dot(x, y)


def test_numpy_operator_matches_csr_spmv():
    rng = np.random.default_rng(11)
    for regime in sorted(GENERATORS):
        A = spd_system(GENERATORS[regime](144, rng))
        part = partition_csr(A, TOPO)
        v = rng.normal(size=(TOPO.nranks, part.rows_per_rank))
        for strategy in ALL_STRATEGIES:
            for overlap in (False, True):
                op = NumpySpMV(part, strategy=strategy, overlap=overlap)
                got = np.asarray(op(v)).reshape(-1)
                np.testing.assert_allclose(
                    got, A.spmv(v.reshape(-1)), rtol=1e-6, atol=1e-9
                )


# ---------------------------------------------------------------------------
# Device path: DistributedSpMV + hierarchical DeviceReductions (serving path)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cg_on_devices_all_strategies_and_overlap(subproc):
    subproc(
        """
import numpy as np
from repro.comm import Compressor
from repro.comm.topology import PodTopology
from repro.sparse import thermal_like, partition_csr, DistributedSpMV
from repro.solve import DeviceReductions, cg, spd_system

rng = np.random.default_rng(0)
topo = PodTopology(npods=2, ppn=4)
A = spd_system(thermal_like(64, rng))
part = partition_csr(A, topo)
b = rng.normal(size=(topo.nranks, part.rows_per_rank)).astype(np.float32)
results = {}
for strat in ("standard", "two_step", "three_step", "split"):
    for ov in (False, True):
        op = DistributedSpMV(part, strategy=strat, use_pallas=True, overlap=ov)
        results[(strat, ov)] = cg(op, b, tol=1e-6)
ref = results[("standard", False)]
assert ref.converged and ref.final_residual <= 1e-6, ref
for key, res in results.items():
    # Pallas kernels make overlap bitwise; histories must agree exactly
    assert res.residuals == ref.residuals, (key, res.residuals[-3:])
    assert res.iterations == ref.iterations, key
want = np.linalg.solve(A.to_dense().astype(np.float64), b.reshape(-1).astype(np.float64))
np.testing.assert_allclose(ref.x.reshape(-1), want, rtol=1e-3, atol=1e-4)

# int8-compressed inter-pod reductions: converges, just less tightly
red = DeviceReductions(topo, compressor=Compressor())
op = DistributedSpMV(part, strategy="two_step")
comp = cg(op, b, tol=1e-4, maxiter=200, reductions=red)
assert comp.converged, comp.final_residual
print("SOLVER DEVICES OK", ref.iterations, "iters")
""",
        devices=8,
    )
