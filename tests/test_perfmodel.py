"""Unit + property tests for the paper's performance models (§2.2, §4)."""

import math

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    LASSEN,
    TPU_V5E_POD,
    CommPattern,
    Locality,
    PatternStats,
    Protocol,
    Space,
    Strategy,
    Transport,
    advise,
    figure43_pattern,
    max_rate,
    postal,
    predict,
    predict_all,
    t_copy,
    t_off,
    t_off_da,
    t_on,
    t_on_split,
)


# ---------------------------------------------------------------------------
# Table 2/3/4 values are reproduced verbatim
# ---------------------------------------------------------------------------


def test_lassen_table2_values():
    p = LASSEN.paths[(Space.CPU, Protocol.SHORT, Locality.ON_SOCKET)]
    assert (p.alpha, p.beta) == (3.67e-07, 1.32e-10)
    p = LASSEN.paths[(Space.GPU, Protocol.RENDEZVOUS, Locality.OFF_NODE)]
    assert (p.alpha, p.beta) == (1.10e-05, 1.72e-10)


def test_lassen_table3_table4():
    assert LASSEN.copy[1].h2d.alpha == 1.30e-05
    assert LASSEN.copy[4].d2h.beta == 1.50e-10
    assert LASSEN.rn_inv == 4.19e-11
    assert LASSEN.procs_per_node == 40
    assert LASSEN.gpus_per_node == 4


def test_protocol_selection():
    assert LASSEN.protocol_for(100, Space.CPU) is Protocol.SHORT
    assert LASSEN.protocol_for(10_000, Space.CPU) is Protocol.EAGER
    assert LASSEN.protocol_for(100_000, Space.CPU) is Protocol.RENDEZVOUS
    # short protocol is never used for device-aware messages (paper §3)
    assert LASSEN.protocol_for(100, Space.GPU) is Protocol.EAGER


# ---------------------------------------------------------------------------
# Primitive model properties
# ---------------------------------------------------------------------------


@given(
    alpha=st.floats(1e-8, 1e-4),
    beta=st.floats(1e-12, 1e-8),
    s=st.integers(1, 10**8),
)
def test_postal_positive_and_monotone(alpha, beta, s):
    t1 = postal(alpha, beta, s)
    t2 = postal(alpha, beta, 2 * s)
    assert t1 > 0 and t2 > t1


@given(
    s_proc=st.integers(1, 10**7),
    ppn=st.integers(1, 64),
    nmsgs=st.integers(1, 64),
)
def test_max_rate_reduces_to_postal_below_injection_limit(s_proc, ppn, nmsgs):
    """When ppn*R_b < R_N the max-rate model reduces to the postal model
    (paper, below eq. 2.2)."""
    alpha, beta = 1e-6, 1e-9  # R_b = 1e9 B/s
    rn_inv = 1e-11  # R_N = 1e11 B/s
    s_node = ppn * s_proc
    t = max_rate(alpha, beta, nmsgs, s_proc, s_node, rn_inv)
    if ppn * 1e9 < 1e11:
        assert t == pytest.approx(alpha * nmsgs + beta * s_proc)
    assert t >= alpha * nmsgs + max(s_node * rn_inv, 0)


@given(s=st.integers(1, 10**7))
def test_max_rate_injection_bound_dominates_for_many_procs(s):
    alpha, beta, rn_inv = 1e-6, 1e-10, 1e-10  # R_b == R_N
    ppn = 40
    t = max_rate(alpha, beta, 1, s, ppn * s, rn_inv)
    assert t == pytest.approx(alpha + ppn * s * rn_inv)


# ---------------------------------------------------------------------------
# Table 6 composites
# ---------------------------------------------------------------------------


def _stats(s_proc=4096.0, nmsg=32, nodes=4):
    return PatternStats(
        s_proc=s_proc,
        s_node=4 * s_proc,
        s_node_node=4 * s_proc / nodes,
        m_proc_node=nodes,
        m_node_node=max(nmsg // nodes, 1),
        m_proc=nmsg,
        num_dest_nodes=nodes,
    )


def test_all_modeled_pairs_evaluate():
    for machine in (LASSEN, TPU_V5E_POD):
        preds = predict_all(machine, _stats(), include_two_step_one=True)
        assert len(preds) == 10
        assert all(t > 0 and math.isfinite(t) for t in preds.values())


def test_split_device_aware_rejected():
    with pytest.raises(ValueError):
        predict(LASSEN, Strategy.SPLIT_MD, Transport.DEVICE_AWARE, _stats())


def test_two_step_one_is_lower_bound_of_two_step():
    """2-Step 1 is the best case of 2-Step (paper §4.6)."""
    s = _stats()
    for tr in (Transport.STAGED_HOST, Transport.DEVICE_AWARE):
        assert predict(LASSEN, Strategy.TWO_STEP_ONE, tr, s) <= predict(
            LASSEN, Strategy.TWO_STEP, tr, s
        )


@given(scale=st.floats(1.0, 64.0))
def test_models_monotone_in_volume(scale):
    base, scaled = _stats(), _stats(s_proc=4096.0 * scale)
    for (strat, tr), t in predict_all(LASSEN, base).items():
        assert predict(LASSEN, strat, tr, scaled) >= t * 0.999


def test_paper_headline_split_wins_at_high_message_count_many_nodes():
    """Fig 4.3b: Split+MD is most performant for 256 messages to 16 nodes at
    moderate message sizes (staged-through-host strategies dominate)."""
    pat = figure43_pattern(nbytes_per_msg=2048, n_inter_node_msgs=256, n_dest_nodes=16)
    adv = advise(pat, machine="lassen")
    staged = [r for r in adv.ranked if r.transport is Transport.STAGED_HOST]
    # a node-aware staged strategy must beat standard device-aware
    std_da = adv.time_for(Strategy.STANDARD, Transport.DEVICE_AWARE)
    assert staged[0].predicted_time < std_da
    assert adv.time_for(Strategy.SPLIT_MD, Transport.STAGED_HOST) < std_da


def test_duplicate_removal_only_helps_node_aware():
    pat = figure43_pattern(nbytes_per_msg=8192, n_inter_node_msgs=256, n_dest_nodes=16)
    plain = advise(pat, machine="lassen")
    dedup = advise(pat, machine="lassen", duplicate_fraction=0.25)
    assert dedup.time_for(Strategy.STANDARD, Transport.STAGED_HOST) == pytest.approx(
        plain.time_for(Strategy.STANDARD, Transport.STAGED_HOST)
    )
    assert dedup.time_for(Strategy.THREE_STEP, Transport.STAGED_HOST) < plain.time_for(
        Strategy.THREE_STEP, Transport.STAGED_HOST
    )


# ---------------------------------------------------------------------------
# CommPattern -> Table 7 stats
# ---------------------------------------------------------------------------


def test_pattern_stats_by_hand():
    # 2 nodes x 2 ranks; rank0 -> rank2 (100B), rank0 -> rank3 (50B), rank1 -> rank2 (30B)
    pat = CommPattern.from_messages(4, 2, [(0, 2, 100), (0, 3, 50), (1, 2, 30)])
    st_ = pat.stats()
    assert st_.s_proc == 150.0
    assert st_.s_node == 180.0
    assert st_.s_node_node == 180.0
    assert st_.m_node_node == 3
    assert st_.m_proc == 2
    assert st_.m_proc_node == 1
    assert st_.num_dest_nodes == 1


@given(
    ppn=st.integers(1, 4),
    nnodes=st.integers(2, 4),
    seed=st.integers(0, 999),
)
@settings(max_examples=30, deadline=None)
def test_pattern_stats_invariants(ppn, nnodes, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    n = ppn * nnodes
    msgs = []
    for _ in range(rng.integers(1, 20)):
        s, d = rng.integers(0, n, 2)
        if s // ppn != d // ppn:
            msgs.append((int(s), int(d), int(rng.integers(1, 10000))))
    pat = CommPattern.from_messages(n, ppn, msgs)
    stt = pat.stats()
    assert stt.s_node >= stt.s_proc >= 0
    assert stt.s_node >= stt.s_node_node
    assert stt.m_proc >= stt.m_proc_node
