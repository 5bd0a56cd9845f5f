"""Optimizer, data pipeline, checkpoint, watchdog unit tests."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.data import SyntheticTokens
from repro.optim import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm, warmup_cosine
from repro.runtime.watchdog import StragglerWatchdog


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_clip_by_global_norm():
    tree = {"a": jnp.asarray([3.0, 4.0])}  # norm 5
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


@given(step=st.integers(0, 10_000))
def test_schedule_bounds(step):
    cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=100, total_steps=10_000)
    lr = float(warmup_cosine(cfg, jnp.asarray(step)))
    assert 0.0 <= lr <= cfg.peak_lr * (1 + 1e-6)


def test_weight_decay_pulls_to_zero():
    cfg = AdamWConfig(peak_lr=0.05, warmup_steps=1, total_steps=100, weight_decay=1.0)
    params = {"w": jnp.asarray([5.0])}
    state = adamw_init(params)
    for _ in range(100):
        params, state, _ = adamw_update(cfg, params, {"w": jnp.zeros(1)}, state)
    assert float(jnp.abs(params["w"]).max()) < 1.0


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_and_step_addressable():
    d1 = SyntheticTokens(vocab_size=1000, batch=4, seq_len=32, seed=3)
    d2 = SyntheticTokens(vocab_size=1000, batch=4, seq_len=32, seed=3)
    b1, b2 = d1.batch_at(17), d2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d1.batch_at(18)["tokens"], b1["tokens"])
    assert b1["tokens"].shape == (4, 32)
    assert (b1["tokens"] >= 0).all() and (b1["tokens"] < 1000).all()
    # labels are next-token shifted from the same stream
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (4, 3)), "b": jnp.zeros((3,))},
        "nested": {"deep": {"x": jnp.arange(5, dtype=jnp.int32)}},
    }


def test_checkpoint_roundtrip_bitwise():
    with tempfile.TemporaryDirectory() as d:
        state = _state()
        save_checkpoint(d, 7, state, extra={"note": "hi"})
        template = jax.tree.map(lambda x: jnp.zeros_like(x), state)
        restored, manifest = load_checkpoint(d, template)
        assert manifest["step"] == 7 and manifest["extra"]["note"] == "hi"
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_and_gc():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save_async(s, _state(s))
        mgr.wait()
        steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
        assert steps == ["step_00000003", "step_00000004"]
        assert not any(x.endswith(".tmp") for x in os.listdir(d))


def test_checkpoint_shape_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"w": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            load_checkpoint(d, {"w": jnp.zeros((3, 3))})


# ---------------------------------------------------------------------------
# straggler watchdog
# ---------------------------------------------------------------------------


def test_watchdog_detects_consecutive_stragglers(monkeypatch):
    times = iter([0.0, 1.0,  # step 0: 1s  (prime EMA)
                  2.0, 3.0,  # step 1: 1s
                  4.0, 9.0,  # step 2: 5s straggler
                  10.0, 15.0,  # step 3: 5s straggler
                  16.0, 21.0])  # step 4: 5s straggler -> escalate
    import repro.runtime.watchdog as W

    monkeypatch.setattr(W.time, "monotonic", lambda: next(times))
    wd = StragglerWatchdog(factor=3.0, budget=3)
    outcomes = []
    for step in range(5):
        wd.start_step()
        outcomes.append(wd.end_step(step))
    assert outcomes == [False, False, False, False, True]
    assert len(wd.events) == 3


def test_watchdog_end_step_without_start_raises():
    wd = StragglerWatchdog()
    with pytest.raises(RuntimeError, match="start_step"):
        wd.end_step(0)
    # a normal step still works afterwards, and consumes its timestamp:
    # a second end_step for the same step is the same clear error, not a
    # TypeError on the None timestamp
    wd.start_step()
    assert wd.end_step(0) is False
    with pytest.raises(RuntimeError, match="start_step"):
        wd.end_step(0)


def test_watchdog_record_external_shares_budget(monkeypatch):
    wd = StragglerWatchdog(budget=3)
    assert wd.record_external("exchange_integrity", {"codec": "bf16"}) is False
    assert wd.record_external("exchange_integrity") is False
    assert wd.record_external("exchange_integrity") is True  # budget hit
    assert len(wd.events) == 3
    assert wd.events[0] == {"kind": "exchange_integrity", "codec": "bf16"}
    # a healthy timed step resets the consecutive count
    times = iter([0.0, 1.0, 2.0, 3.0])
    import repro.runtime.watchdog as W

    monkeypatch.setattr(W.time, "monotonic", lambda: next(times))
    wd.start_step()
    wd.end_step(0)  # primes the EMA
    wd.start_step()
    wd.end_step(1)
    assert wd.consecutive == 0
