"""Strategy execution tests: every node-aware strategy delivers the
reference exchange (8-device subprocess), plus in-process plan properties."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm.exchange import execute_numpy, plan, random_pattern, simulate
from repro.comm.fusion import fuse
from repro.comm.topology import PodTopology


# ---------------------------------------------------------------------------
# In-process: symbolic simulator proves token delivery for random patterns
# ---------------------------------------------------------------------------


@given(
    seed=st.integers(0, 500),
    npods=st.sampled_from([2, 3]),
    ppn=st.sampled_from([2, 4]),
    strategy=st.sampled_from(["standard", "two_step", "three_step", "split"]),
)
@settings(max_examples=40, deadline=None)
def test_all_strategies_deliver_canonical_layout(seed, npods, ppn, strategy):
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=npods, ppn=ppn)
    pat = random_pattern(rng, topo, local_size=6, p_connect=0.5, max_elems=4)
    # plan() runs the symbolic simulator and raises on any mis-delivery
    sp = plan(strategy, pat, message_cap_bytes=48)
    buf = simulate(sp)
    for r in range(topo.nranks):
        want = pat.canonical_tokens(r)
        assert buf[r][: len(want)] == want


@given(seed=st.integers(0, 200))
@settings(max_examples=25, deadline=None)
def test_node_aware_reduces_inter_pod_bytes(seed):
    """The paper's data-redundancy elimination: 2-Step/3-Step/Split move
    fewer inter-pod payload bytes than Standard whenever duplicates exist."""
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=2, ppn=4)
    pat = random_pattern(rng, topo, local_size=5, p_connect=0.7, max_elems=4)
    std = plan("standard", pat)
    for s in ("two_step", "three_step", "split"):
        nodeaware = plan(s, pat, message_cap_bytes=64)
        assert nodeaware.inter_pod_bytes <= std.inter_pod_bytes


@given(
    seed=st.integers(0, 300),
    strategy=st.sampled_from(["standard", "two_step", "three_step", "split"]),
    k=st.sampled_from([2, 3, 5]),
    fused=st.sampled_from([False, True]),
)
@settings(max_examples=30, deadline=None)
def test_batched_exchange_equals_stacked_columns(seed, strategy, k, fused):
    """A batched [nranks, L, k] payload through one plan must equal k stacked
    k=1 exchanges column-for-column (fused and unfused programs)."""
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=2, ppn=2)
    pat = random_pattern(rng, topo, local_size=5, p_connect=0.5, max_elems=3)
    sp = plan(strategy, pat, message_cap_bytes=48)
    if fused:
        sp = fuse(sp)
    local = rng.normal(size=(topo.nranks, 5, k)).astype(np.float32)
    batched = execute_numpy(sp, local)
    for c in range(k):
        single = execute_numpy(sp, local[:, :, c])
        np.testing.assert_array_equal(batched[:, :, c], single)
    np.testing.assert_array_equal(batched[:, : pat.max_recv_size()], pat.reference(local))


def test_three_step_single_message_per_pod_pair():
    rng = np.random.default_rng(3)
    topo = PodTopology(npods=3, ppn=2)
    pat = random_pattern(rng, topo, local_size=4, p_connect=0.8, max_elems=3)
    sp = plan("three_step", pat)
    # inter-pod messages = PermuteWorld rounds: exactly one per ordered pod pair
    from repro.comm.exchange import PermuteWorld

    perms = [st_ for st_ in sp.stages if isinstance(st_, PermuteWorld)]
    assert len(perms) == 1
    n_msgs = sum(len(r) for r in perms[0].rounds)
    assert n_msgs == topo.npods * (topo.npods - 1)


# ---------------------------------------------------------------------------
# 8-device subprocess: numeric execution through shard_map collectives
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_strategies_numeric_on_devices(subproc):
    subproc(
        """
import numpy as np
from repro.comm.topology import PodTopology
from repro.comm.exchange import random_pattern
from repro.comm.strategies import IrregularExchange, STRATEGY_NAMES

rng = np.random.default_rng(7)
topo = PodTopology(npods=2, ppn=4)
for trial in range(2):
    pat = random_pattern(rng, topo, local_size=7, p_connect=0.6, max_elems=5)
    local = rng.normal(size=(topo.nranks, 7)).astype(np.float32)
    ref = pat.reference(local)
    H = pat.max_recv_size()
    for strat in STRATEGY_NAMES:
        ex = IrregularExchange(pat, strat, message_cap_bytes=32)
        out = np.asarray(ex(local))
        np.testing.assert_allclose(out[:, :H], ref[:, :H])
        # unfused program delivers the same bits through real collectives
        exu = IrregularExchange(pat, strat, message_cap_bytes=32, fuse_program=False)
        np.testing.assert_array_equal(np.asarray(exu(local)), out)
    # batched payload [nranks, L, k]: one plan, k columns, every strategy,
    # fused and unfused -- must equal k stacked k=1 calls column-for-column
    loc3 = rng.normal(size=(topo.nranks, 7, 3)).astype(np.float32)
    ref3 = pat.reference(loc3)
    for strat in STRATEGY_NAMES:
        for fused in (True, False):
            ex = IrregularExchange(pat, strat, message_cap_bytes=32,
                                   fuse_program=fused)
            got = np.asarray(ex(loc3))
            np.testing.assert_array_equal(got[:, :H], ref3[:, :H])
            for c in range(3):
                np.testing.assert_array_equal(
                    got[:, :, c], np.asarray(ex(loc3[:, :, c]))
                )
print("OK")
""",
        devices=8,
    )
