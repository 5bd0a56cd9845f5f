"""Split-phase (overlap) equivalence: the two-phase exchange and the
overlapped SpMV/SpMM pipeline must be bitwise-compatible with the barrier
path for every strategy -- on the numpy executor in-process, and through
real shard_map collectives in an 8-device subprocess.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm.exchange import (
    execute_numpy,
    merge_split_phase,
    plan,
    plan_local,
    random_pattern,
    split_phase,
)
from repro.comm.topology import PodTopology
from repro.core.split_plan import split_rows

ALL_STRATEGIES = ("standard", "two_step", "three_step", "split")


# ---------------------------------------------------------------------------
# Numpy executor: split-phase == barrier, bit for bit, every strategy
# ---------------------------------------------------------------------------


@given(
    seed=st.integers(0, 400),
    npods=st.sampled_from([1, 2, 3]),
    ppn=st.sampled_from([1, 2, 4]),
    strategy=st.sampled_from(list(ALL_STRATEGIES)),
    k=st.sampled_from([0, 2, 4]),
)
@settings(max_examples=40, deadline=None)
def test_split_phase_equals_barrier_numpy(seed, npods, ppn, strategy, k):
    """merge(local phase, remote phase) must equal the unsplit program's
    output exactly, for scalar and batched payloads."""
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=npods, ppn=ppn)
    pat = random_pattern(rng, topo, local_size=5, p_connect=0.5, max_elems=4)
    sp = split_phase(pat)
    lp = plan("local", sp.local)
    rp = plan(strategy, sp.remote, message_cap_bytes=48)
    full = plan(strategy, pat, message_cap_bytes=48)
    shape = (topo.nranks, 5) if k == 0 else (topo.nranks, 5, k)
    local = rng.normal(size=shape).astype(np.float32)
    merged = merge_split_phase(
        sp, execute_numpy(lp, local), execute_numpy(rp, local)
    )
    np.testing.assert_array_equal(merged, execute_numpy(full, local))
    H = pat.max_recv_size()
    np.testing.assert_array_equal(merged[:, :H], pat.reference(local))


@given(seed=st.integers(0, 200), npods=st.sampled_from([2, 3]))
@settings(max_examples=20, deadline=None)
def test_split_phase_partition_is_exact(seed, npods):
    """The local/remote sub-patterns partition the needs, and every merge
    slot routes to exactly one phase."""
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=npods, ppn=3)
    pat = random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=3)
    sp = split_phase(pat)
    assert len(sp.local.needs) + len(sp.remote.needs) == len(pat.needs)
    for n in sp.local.needs:
        assert topo.pod_of(n.src) == topo.pod_of(n.dst)
    for n in sp.remote.needs:
        assert topo.pod_of(n.src) != topo.pod_of(n.dst)
    # per-rank: local slots + remote slots == canonical length
    for r in range(topo.nranks):
        n_valid = int(sp.valid[r].sum())
        assert n_valid == len(pat.canonical_tokens(r))
        assert int(sp.from_local[r].sum()) == len(sp.local.canonical_tokens(r))
        assert n_valid - int(sp.from_local[r].sum()) == len(
            sp.remote.canonical_tokens(r)
        )


def test_plan_local_rejects_inter_pod_needs():
    rng = np.random.default_rng(0)
    topo = PodTopology(npods=2, ppn=2)
    # force at least one inter-pod need
    for _ in range(20):
        pat = random_pattern(rng, topo, local_size=4, p_connect=0.9)
        if any(topo.pod_of(n.src) != topo.pod_of(n.dst) for n in pat.needs):
            break
    with pytest.raises(ValueError, match="pod-local"):
        plan_local(pat)


def test_local_phase_moves_no_inter_pod_bytes():
    """The on-node phase must never touch the inter-pod fabric."""
    rng = np.random.default_rng(3)
    topo = PodTopology(npods=3, ppn=4)
    for _ in range(5):
        pat = random_pattern(rng, topo, local_size=6, p_connect=0.6)
        sp = split_phase(pat)
        lp = plan("local", sp.local)
        assert lp.inter_pod_bytes == 0
        assert lp.wire_inter_pod_bytes == 0


# ---------------------------------------------------------------------------
# Interior/boundary row split
# ---------------------------------------------------------------------------


def test_split_rows_tile_granularity():
    dep = np.zeros((2, 10), dtype=bool)
    dep[0, 3] = True  # one boundary row poisons its whole tile
    s = split_rows(dep, tile_rows=4)
    assert s.interior_tiles.shape == (2, 3)  # ceil(10/4)
    np.testing.assert_array_equal(s.interior_tiles[0], [False, True, True])
    np.testing.assert_array_equal(s.interior_tiles[1], [True, True, True])
    np.testing.assert_array_equal(s.interior, ~dep)
    assert s.interior_fraction == pytest.approx(19 / 20)
    assert s.interior_tile_fraction == pytest.approx(5 / 6)
    assert s.interior_tile_fraction <= s.interior_fraction


def test_split_rows_edge_cases():
    # all-boundary and all-interior
    s = split_rows(np.ones((1, 8), dtype=bool), tile_rows=8)
    assert s.interior_fraction == 0.0 and s.interior_tile_fraction == 0.0
    s = split_rows(np.zeros((1, 8), dtype=bool), tile_rows=256)
    assert s.interior_fraction == 1.0 and s.interior_tile_fraction == 1.0
    # padding rows count as interior, boundary property is the complement
    s = split_rows(np.array([[True, False, False]]), tile_rows=2)
    np.testing.assert_array_equal(s.interior_tiles, [[False, True]])
    np.testing.assert_array_equal(s.boundary_tiles, [[True, False]])
    with pytest.raises(ValueError):
        split_rows(np.zeros((3,), dtype=bool), tile_rows=2)
    with pytest.raises(ValueError):
        split_rows(np.zeros((1, 3), dtype=bool), tile_rows=0)


# ---------------------------------------------------------------------------
# 8-device subprocess: real collectives, every strategy, exchange + SpMV
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_split_phase_exchange_on_devices(subproc):
    subproc(
        """
import numpy as np
from repro.comm.topology import PodTopology
from repro.comm.exchange import random_pattern
from repro.comm.strategies import IrregularExchange, STRATEGY_NAMES

rng = np.random.default_rng(11)
topo = PodTopology(npods=2, ppn=4)
for trial in range(2):
    pat = random_pattern(rng, topo, local_size=6, p_connect=0.6, max_elems=4)
    local = rng.normal(size=(topo.nranks, 6)).astype(np.float32)
    loc3 = rng.normal(size=(topo.nranks, 6, 3)).astype(np.float32)
    for strat in STRATEGY_NAMES:
        ex = IrregularExchange(pat, strat, message_cap_bytes=32)
        barrier = np.asarray(ex(local))
        h = ex.start(local)
        np.testing.assert_array_equal(np.asarray(h.finish()), barrier)
        # the fast phase only carries on-pod tokens; spot-check its values
        # against the local sub-pattern's reference
        from repro.comm.exchange import split_phase
        sp = split_phase(pat)
        np.testing.assert_array_equal(
            np.asarray(h.local_halo)[:, : sp.local.max_recv_size()],
            sp.local.reference(local),
        )
        # batched payload through the same handle
        h3 = ex.start(loc3)
        np.testing.assert_array_equal(np.asarray(h3.finish()), np.asarray(ex(loc3)))
print("OK")
""",
        devices=8,
    )


@pytest.mark.slow
def test_overlapped_spmv_on_devices(subproc):
    subproc(
        """
import numpy as np
from repro.comm.topology import PodTopology
from repro.sparse import build, thermal_like

rng = np.random.default_rng(0)
topo = PodTopology(npods=2, ppn=4)
A = thermal_like(256, rng)
v = rng.normal(size=(A.n,)).astype(np.float32)
vr = v.reshape(topo.nranks, -1)
V = rng.normal(size=(A.n, 3)).astype(np.float32)
Vr = V.reshape(topo.nranks, -1, 3)
for use_pallas in (True, False):
    for strat in ("standard", "two_step", "three_step", "split"):
        sp = build(A, topo, strategy=strat, use_pallas=use_pallas)
        ov = build(A, topo, strategy=strat, use_pallas=use_pallas, overlap=True)
        if use_pallas:
            # pallas kernels are opaque to XLA fusion, so the overlapped
            # diag-pass + off-pass composition is BITWISE equal to the
            # barrier program's fused diag+off (the serving-path guarantee)
            np.testing.assert_array_equal(np.asarray(ov(vr)), np.asarray(sp(vr)))
            np.testing.assert_array_equal(
                np.asarray(ov.matmat(Vr)), np.asarray(sp.matmat(Vr))
            )
        else:
            # the jnp-oracle barrier program fuses its two reductions under
            # one jit and XLA's codegen for that fused form differs from
            # the split two-program form by ~1 ulp; the halo itself is
            # bitwise equal (exchange tests above), so allow ulp-level slack
            np.testing.assert_allclose(
                np.asarray(ov(vr)), np.asarray(sp(vr)), rtol=1e-6, atol=1e-6
            )
            np.testing.assert_allclose(
                np.asarray(ov.matmat(Vr)), np.asarray(sp.matmat(Vr)),
                rtol=1e-6, atol=1e-6,
            )
        np.testing.assert_allclose(
            np.asarray(ov(vr)).reshape(-1), A.spmv(v), rtol=1e-4, atol=1e-4
        )
print("OK")
""",
        devices=8,
    )
