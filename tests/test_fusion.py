"""Fusion-pass properties: fused programs are equivalent and never cost more.

The oracle chain is three-deep: the vectorized token simulator (checked
inside ``fuse`` itself), the jax-free numpy value executor, and
``ExchangePattern.reference``.  Fused and unfused programs must agree
bit-for-bit on all of them, for every strategy.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import _legacy_planner as legacy
from repro.comm.exchange import (
    A2ALocal,
    A2APod,
    Gather,
    PermuteWorld,
    execute_numpy,
    plan,
    random_pattern,
)
from repro.comm.fusion import compose_gathers, fuse, fuse_stages
from repro.comm.topology import PodTopology

STRATEGIES = ("standard", "two_step", "three_step", "split")


# ---------------------------------------------------------------------------
# Property: fused == unfused == reference, and wire bytes never increase
# ---------------------------------------------------------------------------


@given(
    seed=st.integers(0, 500),
    npods=st.sampled_from([2, 3]),
    ppn=st.sampled_from([2, 4]),
    strategy=st.sampled_from(list(STRATEGIES)),
)
@settings(max_examples=40, deadline=None)
def test_fused_bit_identical_to_unfused_and_reference(seed, npods, ppn, strategy):
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=npods, ppn=ppn)
    pat = random_pattern(rng, topo, local_size=6, p_connect=0.5, max_elems=4)
    sp = plan(strategy, pat, message_cap_bytes=48)
    fp = fuse(sp)  # verify=True replays the token simulator internally

    local = rng.normal(size=(topo.nranks, 6)).astype(np.float32)
    ref = pat.reference(local)
    H = pat.max_recv_size()
    out_unfused = execute_numpy(sp, local)
    out_fused = execute_numpy(fp, local)
    # bit-identical: pure data movement, no arithmetic
    np.testing.assert_array_equal(out_fused, out_unfused)
    np.testing.assert_array_equal(out_fused[:, :H], ref[:, :H])

    # wire bytes never increase (fusion only drops on-device gathers)
    assert fp.wire_intra_pod_bytes <= sp.wire_intra_pod_bytes
    assert fp.wire_inter_pod_bytes <= sp.wire_inter_pod_bytes
    assert fp.intra_pod_bytes == sp.intra_pod_bytes
    assert fp.inter_pod_bytes == sp.inter_pod_bytes
    # and the program got strictly shorter (every strategy starts with a
    # Gather feeding a collective)
    assert len(fp.stages) < len(sp.stages)
    assert fp.fused and not sp.fused


@given(seed=st.integers(0, 200))
@settings(max_examples=10, deadline=None)
def test_fused_batched_payloads_match_reference(seed):
    """Trailing feature dims ride along unchanged through fused programs."""
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=2, ppn=2)
    pat = random_pattern(rng, topo, local_size=5, p_connect=0.6, max_elems=3)
    local = rng.normal(size=(topo.nranks, 5, 3)).astype(np.float32)
    ref = pat.reference(local)
    H = pat.max_recv_size()
    for strategy in STRATEGIES:
        fp = fuse(plan(strategy, pat, message_cap_bytes=32))
        out = execute_numpy(fp, local)
        np.testing.assert_array_equal(out[:, :H], ref[:, :H])


# ---------------------------------------------------------------------------
# Planner parity: the vectorized planner reproduces the legacy programs
# ---------------------------------------------------------------------------


def _assert_plans_equal(a, b):
    assert len(a.stages) == len(b.stages)
    for s, t in zip(a.stages, b.stages):
        assert type(s) is type(t)
        if isinstance(s, Gather):
            np.testing.assert_array_equal(s.idx, t.idx)
        elif isinstance(s, (A2ALocal, A2APod)):
            assert s.buflen == t.buflen
        elif isinstance(s, PermuteWorld):
            assert s.rounds == t.rounds and s.blks == t.blks
            for u, v in zip(s.sels, t.sels):
                np.testing.assert_array_equal(u, v)
    for f in (
        "out_size",
        "intra_pod_bytes",
        "inter_pod_bytes",
        "wire_intra_pod_bytes",
        "wire_inter_pod_bytes",
    ):
        assert getattr(a, f) == getattr(b, f), f


@given(
    seed=st.integers(0, 300),
    strategy=st.sampled_from(list(STRATEGIES)),
)
@settings(max_examples=20, deadline=None)
def test_vectorized_planner_matches_legacy(seed, strategy):
    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=int(rng.integers(2, 4)), ppn=int(rng.integers(2, 5)))
    L = int(rng.integers(3, 8))
    pat = random_pattern(
        rng, topo, local_size=L, p_connect=float(rng.uniform(0.1, 0.9)),
        max_elems=min(5, L),
    )
    cap = int(rng.integers(16, 128))
    _assert_plans_equal(
        plan(strategy, pat, message_cap_bytes=cap),
        legacy.plan(strategy, pat, message_cap_bytes=cap),
    )


# ---------------------------------------------------------------------------
# Rewrite unit tests
# ---------------------------------------------------------------------------


def test_adjacent_gathers_compose_to_one():
    """R1: Gather;Gather -> one Gather with the composed index map."""
    # 1-rank program, local = [a, b, c]: w_in = 0, L = 3, so ext0 = local
    # with PAD sentinel 3.  g1 picks [c, a, PAD];
    # g2 picks [g1[2](PAD), g1[0](c), local b, PAD]
    g1 = np.array([[2, 0, 3]], dtype=np.int32)
    # ext1 = concat(g1_out(3), local(3)), sentinel 6
    g2 = np.array([[2, 0, 4, 6]], dtype=np.int32)
    fused = compose_gathers(g1, g2, w_in=0, local_size=3)
    np.testing.assert_array_equal(fused, [[3, 2, 1, 3]])

    stages = fuse_stages((Gather(idx=g1), Gather(idx=g2)), local_size=3)
    assert len(stages) == 1 and isinstance(stages[0], Gather)
    np.testing.assert_array_equal(stages[0].idx, fused)


def test_identity_gather_dropped():
    """R4: an identity Gather on the current buffer is eliminated."""
    g = np.array([[0, 1], [1, 0]], dtype=np.int32)  # L=2, w=0: reads local
    ident = np.array([[0, 1], [0, 1]], dtype=np.int32)  # identity on width-2 buf
    stages = fuse_stages((Gather(idx=g), Gather(idx=ident)), local_size=2)
    assert len(stages) == 1
    np.testing.assert_array_equal(stages[0].idx, g)


def test_gather_folds_into_a2a_input_layout():
    """R2: Gather feeding an A2A becomes the collective's idx."""
    rng = np.random.default_rng(0)
    topo = PodTopology(npods=2, ppn=2)
    pat = random_pattern(rng, topo, local_size=4, p_connect=0.8, max_elems=3)
    fp = fuse(plan("standard", pat))
    kinds = [type(s).__name__ for s in fp.stages]
    assert kinds == ["A2APod", "A2ALocal", "Gather"]
    assert fp.stages[0].idx is not None and fp.stages[1].idx is not None
