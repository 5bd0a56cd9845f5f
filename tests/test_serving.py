"""Serving front-end: continuous batching, admission, and the deterministic
traffic simulator (``repro.serving``).

The scheduler invariants are property-tested over seeded traces:

* FIFO within a fingerprint class (batches are lane prefixes);
* batch width never exceeds ``max_width`` or the memory budget;
* ripe lanes dispatch oldest-deadline-first (the no-starvation discipline);
* identical seeds produce identical event traces and identical p50/p99.

Also covers the ``launch/serve.py::routing_counts`` ragged source-rank
binning regression and the >= 3x coalescing-throughput acceptance pin.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import PodTopology, random_pattern
from repro.runtime import AdmissionController, StragglerWatchdog
from repro.serving import (
    ContinuousBatcher,
    Request,
    RequestQueue,
    SimConfig,
    WorkloadClass,
    sequential_baseline,
    serving_report,
    simulate,
)
from repro.testing import make_trace, zipf_weights

TOPO = PodTopology(npods=2, ppn=4)


def _classes(n=4, local_size=32, max_elems=4):
    out = {}
    for i in range(n):
        pat = random_pattern(
            np.random.default_rng(100 + i), TOPO,
            local_size=local_size, max_elems=max_elems,
        )
        out[f"c{i}"] = WorkloadClass.from_pattern(pat, fp=f"c{i}")
    return out


CLASSES = _classes()
FPS = sorted(CLASSES)


def _check_schedule(events, window, caps):
    """Replay the event trace and assert every scheduling invariant.

    Reconstructs the queue from arrive/dispatch events; at each dispatch
    the batch must be (a) a FIFO prefix of its lane, (b) within the width
    cap, (c) from a ripe lane, and (d) the ripe lane with the OLDEST
    deadline -- the discipline that bounds waiting.
    """
    pending = {}  # fp -> [(arrival, rid), ...] in admission order
    for ev in events:
        if ev[0] == "arrive":
            _, t, rid, fp = ev
            pending.setdefault(fp, []).append((t, rid))
        elif ev[0] == "dispatch":
            _, t, fp, width, _key, rids = ev
            ripe = {}
            for f, lane in pending.items():
                if not lane:
                    continue
                deadline = lane[0][0] + window
                if deadline <= t or len(lane) >= caps[f]:
                    ripe[f] = deadline
            assert fp in ripe, f"dispatched unripe lane {fp} at t={t}"
            assert ripe[fp] == min(ripe.values()), "not oldest-deadline-first"
            lane = pending[fp]
            assert width <= caps[fp], f"width {width} exceeds cap {caps[fp]}"
            assert [r for _, r in lane[:width]] == list(rids), "not a FIFO prefix"
            del lane[:width]
    for fp, lane in pending.items():
        assert not lane, f"admitted requests of {fp} never dispatched: {lane}"


class TestSimulatorDeterminism:
    def test_same_seed_identical_everything(self):
        trace = make_trace(11, 300, FPS, pattern="poisson", rate=30000.0, skew=1.1)
        cfg = SimConfig(window=1e-3, max_width=8)
        r1 = simulate(CLASSES, trace, cfg)
        r2 = simulate(_classes(), make_trace(
            11, 300, FPS, pattern="poisson", rate=30000.0, skew=1.1), cfg)
        assert r1.events == r2.events
        assert r1.trace_hash == r2.trace_hash
        assert (r1.p50, r1.p99) == (r2.p50, r2.p99)
        assert r1.summary() == r2.summary()

    def test_different_seed_different_trace(self):
        cfg = SimConfig(window=1e-3, max_width=8)
        r1 = simulate(CLASSES, make_trace(1, 200, FPS), cfg)
        r2 = simulate(CLASSES, make_trace(2, 200, FPS), cfg)
        assert r1.trace_hash != r2.trace_hash

    @given(seed=st.integers(0, 10_000), pattern=st.sampled_from(
        ["poisson", "burst", "uniform"]))
    @settings(max_examples=10, deadline=None)
    def test_same_seed_identical_traces_property(self, seed, pattern):
        cfg = SimConfig(window=5e-4, max_width=8)
        mk = lambda: make_trace(seed, 120, FPS, pattern=pattern, rate=40000.0)
        assert simulate(CLASSES, mk(), cfg).events == simulate(CLASSES, mk(), cfg).events


class TestSchedulerInvariants:
    @given(
        seed=st.integers(0, 10_000),
        pattern=st.sampled_from(["poisson", "burst", "uniform"]),
        max_width=st.integers(1, 12),
        window_us=st.integers(0, 2000),
    )
    @settings(max_examples=15, deadline=None)
    def test_fifo_width_and_deadline_order(self, seed, pattern, max_width, window_us):
        window = window_us * 1e-6
        cfg = SimConfig(window=window, max_width=max_width)
        trace = make_trace(seed, 150, FPS, pattern=pattern, rate=50000.0, skew=1.3)
        res = simulate(CLASSES, trace, cfg)
        caps = {fp: max_width for fp in FPS}
        _check_schedule(res.events, window, caps)
        assert res.completed + res.rejected == len(trace)

    @given(seed=st.integers(0, 10_000), cap_requests=st.integers(1, 6))
    @settings(max_examples=10, deadline=None)
    def test_memory_budget_caps_width(self, seed, cap_requests):
        bpr = max(c.bytes_per_request for c in CLASSES.values())
        budget = bpr * cap_requests
        cfg = SimConfig(window=1e-3, max_width=8, memory_budget=budget)
        trace = make_trace(seed, 120, FPS, pattern="burst", rate=100000.0, burst=16)
        res = simulate(CLASSES, trace, cfg)
        for ev in res.events:
            if ev[0] == "dispatch":
                fp, width = ev[2], ev[3]
                assert width * CLASSES[fp].bytes_per_request <= budget
        caps = {
            fp: min(8, budget // CLASSES[fp].bytes_per_request) for fp in FPS
        }
        _check_schedule(res.events, 1e-3, caps)

    def test_no_wait_past_deadline_under_light_load(self):
        # A steady trickle well under capacity: every request must dispatch
        # by its coalescing deadline plus the time the executor may already
        # be busy (one max-width batch per class ahead of it).
        cfg = SimConfig(window=2e-3, max_width=8)
        trace = make_trace(5, 200, FPS, pattern="uniform", rate=2000.0)
        res = simulate(CLASSES, trace, cfg)
        batcher = ContinuousBatcher(CLASSES, window=cfg.window, max_width=8)
        t_max = max(
            batcher.advise(fp, 8).best.predicted_time + cfg.host_overhead_s
            for fp in FPS
        )
        bound = cfg.window + len(FPS) * t_max
        arrivals = {r.rid: r.arrival for r in trace}
        for ev in res.events:
            if ev[0] == "dispatch":
                t, rids = ev[1], ev[5]
                for rid in rids:
                    assert t - arrivals[rid] <= bound + 1e-12

    def test_fifo_completion_order_within_class(self):
        trace = make_trace(9, 250, FPS, pattern="burst", rate=80000.0, burst=24)
        res = simulate(CLASSES, trace, SimConfig(window=1e-3, max_width=8))
        admitted, dispatched = {}, {}
        for ev in res.events:
            if ev[0] == "arrive":
                admitted.setdefault(ev[3], []).append(ev[2])
            elif ev[0] == "dispatch":
                dispatched.setdefault(ev[2], []).extend(ev[5])
        assert admitted == dispatched


class TestAdmission:
    def test_controller_counts_and_reset(self):
        ac = AdmissionController(max_queue_depth=2, reject_burst=3)
        assert ac.admit(0) and ac.admit(1)
        assert not ac.admit(2) and not ac.admit(5)
        assert ac.admit(1)  # streak resets on success
        assert (ac.admitted, ac.rejected) == (3, 2)

    def test_rejection_bursts_escalate_through_watchdog(self):
        wd = StragglerWatchdog(budget=2)
        ac = AdmissionController(max_queue_depth=1, watchdog=wd, reject_burst=4)
        ac.admit(0)
        for _ in range(8):  # two full bursts of consecutive rejections
            ac.admit(1)
        assert ac.rejected == 8
        kinds = [e.get("kind") for e in wd.events]
        assert kinds == ["admission_overload", "admission_overload"]
        assert ac.escalations == 1  # second event exhausts budget=2

    def test_overload_sheds_and_still_serves_admitted(self):
        cfg = SimConfig(window=1e-3, max_width=8, max_queue_depth=8)
        trace = make_trace(3, 400, FPS, pattern="burst", rate=1e6, burst=400)
        res = simulate(CLASSES, trace, cfg)
        assert res.rejected > 0
        assert res.completed + res.rejected == len(trace)
        assert res.completed == sum(1 for e in res.events if e[0] == "arrive")
        caps = {fp: 8 for fp in FPS}
        _check_schedule(res.events, cfg.window, caps)

    def test_validation(self):
        with pytest.raises(ValueError):
            AdmissionController(max_queue_depth=0)
        with pytest.raises(ValueError):
            AdmissionController(reject_burst=0)


class TestQueueAndBatcher:
    def test_lanes_are_fifo(self):
        q = RequestQueue()
        for i in range(6):
            assert q.submit(Request(arrival=0.1 * i, rid=i, fp=f"c{i % 2}"))
        assert len(q) == 6
        assert [r.rid for r in q.take("c0", 2)] == [0, 2]
        assert [r.rid for r in q.take("c0", 9)] == [4]
        assert q.peek_oldest("c0") is None
        assert [fp for fp, _, _ in q.lanes()] == ["c1"]

    def test_batcher_validation(self):
        with pytest.raises(ValueError):
            ContinuousBatcher({})
        with pytest.raises(ValueError):
            ContinuousBatcher(CLASSES, max_width=0)
        bpr = min(c.bytes_per_request for c in CLASSES.values())
        with pytest.raises(ValueError):  # budget below one request
            ContinuousBatcher(CLASSES, memory_budget=bpr - 1)
        with pytest.raises(KeyError):
            ContinuousBatcher(CLASSES).submit(Request(0.0, 0, "nope"))

    def test_advice_is_memoized_per_width(self):
        b = ContinuousBatcher(CLASSES, max_width=8)
        a1 = b.advise("c0", 8)
        a2 = b.advise("c0", 8)
        assert a1 is a2
        assert (b.advice_hits, b.advice_misses) == (1, 1)
        b.advise("c0", 4)
        assert b.advice_misses == 2

    def test_batch_strategy_comes_from_advisor(self):
        b = ContinuousBatcher(CLASSES, window=0.0, max_width=8)
        for i in range(8):
            b.submit(Request(arrival=0.0, rid=i, fp="c0"))
        batch = b.next_batch(0.0)
        assert batch is not None and batch.width == 8
        assert batch.payload_width == 8  # base_width 1
        best = b.advise("c0", 8).best
        assert batch.key == best.key
        assert batch.predicted_time == best.predicted_time
        assert batch.strategy in ("standard", "two_step", "three_step", "split")

    def test_workload_class_validation(self):
        cls = CLASSES["c0"]
        with pytest.raises(ValueError):
            WorkloadClass(fp="x", stats=cls.stats, bytes_per_request=0)
        with pytest.raises(ValueError):
            WorkloadClass(fp="x", stats=cls.stats, bytes_per_request=1, base_width=0)
        with pytest.raises(ValueError):  # key / fingerprint mismatch
            ContinuousBatcher({"other": cls})


class TestThroughputAcceptance:
    def test_coalesced_throughput_at_least_3x_sequential(self):
        """Acceptance pin: k=8 coalescing >= 3x sequential dispatch on the
        same skewed-fingerprint burst trace (deterministic model numbers)."""
        trace = make_trace(7, 256, FPS, pattern="burst",
                           rate=200000.0, skew=1.2, burst=32)
        cfg = SimConfig(window=1e-3, max_width=8)
        rep = serving_report(CLASSES, trace, cfg)
        assert rep["speedup"] >= 3.0
        assert rep["coalesced"]["completed"] == 256
        assert rep["sequential"]["completed"] == 256
        assert rep["coalesced"]["p99_s"] < rep["sequential"]["p99_s"]
        assert rep["coalesced"]["mean_width"] > 4.0

    def test_sequential_baseline_is_width_one(self):
        trace = make_trace(4, 60, FPS, pattern="poisson", rate=50000.0)
        res = sequential_baseline(CLASSES, trace, SimConfig(max_width=8))
        assert res.mean_width == 1.0
        assert res.batches == res.completed == 60


class TestTraces:
    def test_zipf_weights(self):
        w = zipf_weights(4, skew=1.0)
        assert np.isclose(w.sum(), 1.0)
        assert all(w[i] > w[i + 1] for i in range(3))
        assert np.allclose(zipf_weights(4, skew=0.0), 0.25)

    def test_trace_shapes_and_validation(self):
        t = make_trace(0, 50, FPS, pattern="uniform", rate=1000.0)
        assert len(t) == 50
        assert [r.rid for r in t] == list(range(50))
        assert all(t[i].arrival <= t[i + 1].arrival for i in range(49))
        with pytest.raises(ValueError):
            make_trace(0, 10, FPS, pattern="nope")
        with pytest.raises(ValueError):
            make_trace(0, 10, FPS, rate=0.0)
        burst = make_trace(0, 32, FPS, pattern="burst", burst=8, rate=8000.0)
        times = sorted({r.arrival for r in burst})
        assert len(times) == 4  # 32 requests in 4 simultaneous groups

    def test_skew_concentrates_on_hot_class(self):
        t = make_trace(0, 500, FPS, skew=1.5)
        hot = sum(1 for r in t if r.fp == FPS[0])
        assert hot > 500 // len(FPS)


class TestRoutingCountsRagged:
    """`launch/serve.py::routing_counts` must bin tokens by their batch
    row's block-sharded owner (np.array_split convention), not by flat
    index -- the two disagree whenever B % nranks != 0."""

    @staticmethod
    def _setup(V=32, M=8, E=8, seed=0):
        from types import SimpleNamespace

        rng = np.random.default_rng(seed)
        params = {
            "embed": rng.standard_normal((V, M)).astype(np.float32),
            "seg_moe": {"moe": {
                "router": rng.standard_normal((1, M, E)).astype(np.float32)
            }},
        }
        cfg = SimpleNamespace(
            family="moe", moe=SimpleNamespace(top_k=2, n_experts=E)
        )
        return params, cfg, rng

    def test_row_sums_match_block_sharding_ragged(self):
        from repro.launch.serve import routing_counts

        params, cfg, rng = self._setup()
        nranks = 4
        B, S = 5, 3  # ragged: 5 % 4 != 0
        tokens = rng.integers(0, 32, (B, S))
        counts = routing_counts(params, cfg, tokens, nranks)
        sizes = np.array([2, 1, 1, 1])  # array_split of 5 rows over 4 ranks
        assert counts.sum() == B * S * cfg.moe.top_k
        np.testing.assert_array_equal(
            counts.sum(axis=1), sizes * S * cfg.moe.top_k
        )

    def test_flat_index_binning_was_wrong_on_ragged(self):
        params, cfg, rng = self._setup()
        nranks = 4
        B, S, k = 5, 3, cfg.moe.top_k
        tokens = rng.integers(0, 32, (B, S))
        # the pre-fix formula splits batch row 1 across ranks 0 and 1
        N = B * S
        old_src = np.repeat(np.arange(N) * nranks // N, k)
        row_of = np.repeat(np.arange(B), S * k)
        owner = np.repeat(np.arange(nranks), [2, 1, 1, 1])
        assert (old_src != owner[row_of]).any()

    def test_equal_split_unchanged(self):
        from repro.launch.serve import routing_counts

        params, cfg, rng = self._setup()
        nranks = 4
        B, S, k = 8, 4, cfg.moe.top_k
        tokens = rng.integers(0, 32, (B, S))
        counts = routing_counts(params, cfg, tokens, nranks)
        # old flat-index binning agrees exactly when B % nranks == 0
        toks = tokens.reshape(-1)
        logits = params["embed"][toks] @ np.asarray(
            params["seg_moe"]["moe"]["router"])[0]
        top = np.argsort(-logits, axis=-1)[:, :k]
        e_per = cfg.moe.n_experts // nranks
        src = np.repeat(np.arange(toks.size) * nranks // toks.size, k)
        dst = np.minimum(top.reshape(-1) // e_per, nranks - 1)
        old = np.zeros((nranks, nranks), dtype=np.int64)
        np.add.at(old, (src, dst), 1)
        np.testing.assert_array_equal(counts, old)

    def test_flat_token_stream(self):
        from repro.launch.serve import routing_counts

        params, cfg, rng = self._setup()
        nranks = 4
        tokens = rng.integers(0, 32, 10)  # flat [N]: N % nranks != 0
        counts = routing_counts(params, cfg, tokens, nranks)
        np.testing.assert_array_equal(
            counts.sum(axis=1), np.array([3, 3, 2, 2]) * cfg.moe.top_k
        )

    def test_from_routing_workload_class(self):
        from repro.launch.serve import routing_counts

        params, cfg, rng = self._setup()
        counts = routing_counts(params, cfg, rng.integers(0, 32, (8, 4)), 8)
        cls = WorkloadClass.from_routing(counts, ppn=4, d_model=16, fp="moe")
        assert cls.kind == "moe"
        assert cls.base_width == 16
        assert cls.bytes_per_request == int(counts.sum()) * 16 * 4
