"""Property tests for Algorithm 1 (Split setup)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CommPattern, Message, build_split_plan


def random_pattern(rng, ppn, nnodes, max_msgs=30, max_bytes=5000):
    n = ppn * nnodes
    msgs = []
    for _ in range(rng.integers(1, max_msgs)):
        s, d = rng.integers(0, n, 2)
        if s != d:
            msgs.append(Message(int(s), int(d), int(rng.integers(1, max_bytes))))
    return CommPattern.from_messages(n, ppn, msgs)


@given(
    ppn=st.integers(1, 6),
    nnodes=st.integers(2, 5),
    cap=st.integers(1, 8192),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=80, deadline=None)
def test_algorithm1_invariants(ppn, nnodes, cap, seed):
    rng = np.random.default_rng(seed)
    pat = random_pattern(rng, ppn, nnodes)
    plan = build_split_plan(pat, message_cap=cap)

    inter = pat.inter_node_messages()
    total_inter = sum(m.nbytes for m in inter)

    # 1. byte conservation: every inter-node byte is carried by exactly one chunk
    assert plan.total_inter_node_bytes() == total_inter
    covered = {}
    for c in plan.chunks:
        for msg, off, length in c.parts:
            covered.setdefault(id(msg), 0)
            covered[id(msg)] += length
    for m in inter:
        assert covered.get(id(m), 0) == m.nbytes

    # 2. chunk sizes respect the effective cap (lines 12-17)
    for c in plan.chunks:
        eff = plan.effective_cap[c.dest_node]
        assert c.nbytes <= eff

    # 3. locality: sender on origin node, receiver on destination node
    for c in plan.chunks:
        assert pat.node_of(c.sender) == c.origin_node
        assert pat.node_of(c.receiver) == c.dest_node
        assert c.origin_node != c.dest_node

    # 4. line 18 balance: receive counts per node differ by at most 1
    from collections import Counter

    per_node = {}
    for c in plan.chunks:
        per_node.setdefault(c.dest_node, Counter())[c.receiver] += 1
    for node, counts in per_node.items():
        n_chunks = sum(counts.values())
        expected_max = -(-n_chunks // ppn)
        assert max(counts.values()) <= expected_max

    # 5. on-node messages are untouched (handled by local_comm)
    assert sum(m.nbytes for m in plan.local_messages) == sum(
        m.nbytes for m in pat.messages
    ) - total_inter


def test_conglomeration_when_below_cap():
    """Lines 12-13: if max node->node volume < cap, one chunk per origin."""
    pat = CommPattern.from_messages(
        8, 4, [(0, 4, 10), (1, 5, 20), (2, 6, 30)]
    )
    plan = build_split_plan(pat, message_cap=1000)
    assert len(plan.chunks) == 1  # all three messages fused: same origin/dest node
    assert plan.chunks[0].nbytes == 60


def test_cap_raised_when_exceeding_ppn_chunks():
    """Lines 14-17: cap rises to ceil(total/PPN) when too many chunks."""
    ppn = 2
    msgs = [(0, 2 + (i % 2), 100) for i in range(10)]  # 1000B node0 -> node1
    pat = CommPattern.from_messages(4, ppn, msgs)
    plan = build_split_plan(pat, message_cap=10)  # would need 100 chunks > ppn
    assert plan.effective_cap[1] == 500  # ceil(1000/2)
    assert len(plan.chunks) == 2


def test_invalid_cap_rejected():
    pat = CommPattern.from_messages(4, 2, [(0, 2, 10)])
    with pytest.raises(ValueError):
        build_split_plan(pat, message_cap=0)


# ---------------------------------------------------------------------------
# Cap-resolution edge cases (previously only hit through random patterns)
# ---------------------------------------------------------------------------


def test_cap_larger_than_total_volume():
    """Cap >> everything: lines 12-13 conglomerate to one chunk per origin
    node and the effective cap collapses to the largest origin volume."""
    pat = CommPattern.from_messages(
        12, 4,
        [(0, 4, 100), (1, 5, 50), (8, 6, 30), (9, 7, 20)],  # node0+node2 -> node1
    )
    plan = build_split_plan(pat, message_cap=10**9)
    assert len(plan.chunks) == 2  # one per origin node (0 and 2)
    assert {(c.origin_node, c.nbytes) for c in plan.chunks} == {(0, 150), (2, 50)}
    assert plan.effective_cap[1] == 150  # max origin volume, not the user cap
    # conglomerated chunks need no inter-node splitting of any message
    for c in plan.chunks:
        for msg, off, length in c.parts:
            assert (off, length) == (0, msg.nbytes)


def test_single_node_world_has_no_chunks():
    """All traffic on one node: Algorithm 1 degenerates to local_comm."""
    pat = CommPattern.from_messages(4, 4, [(0, 1, 64), (2, 3, 32), (1, 2, 8)])
    plan = build_split_plan(pat, message_cap=16)
    assert plan.chunks == ()
    assert plan.effective_cap == {}
    assert plan.total_inter_node_bytes() == 0
    assert sum(m.nbytes for m in plan.local_messages) == 104
    assert plan.send_redistribution() == [] and plan.recv_redistribution() == []


def test_ppn1_world_assignment():
    """PPN=1: every node is one rank, so line 18's balancing must pin the
    sender/receiver to the only rank on each node and still split by cap."""
    pat = CommPattern.from_messages(3, 1, [(0, 1, 100), (2, 1, 40)])
    plan = build_split_plan(pat, message_cap=30)
    # total 140 / cap 30 > ppn=1 -> cap raised to ceil(140/1) = 140 (line 16)
    assert plan.effective_cap[1] == 140
    assert all(c.receiver == 1 for c in plan.chunks)
    for c in plan.chunks:
        assert c.sender == c.origin_node  # rank == node when ppn == 1
    assert plan.total_inter_node_bytes() == 140


def test_ppn1_cap_not_raised_when_chunks_fit():
    """PPN=1 with cap >= total: conglomeration branch, one chunk per origin."""
    pat = CommPattern.from_messages(2, 1, [(0, 1, 10)])
    plan = build_split_plan(pat, message_cap=1000)
    assert len(plan.chunks) == 1
    assert plan.chunks[0].sender == 0 and plan.chunks[0].receiver == 1
