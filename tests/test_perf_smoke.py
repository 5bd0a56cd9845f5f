"""Setup-path performance regression guards.

These are deliberately generous budgets: they exist to catch an accidental
return to per-token Python loops (orders of magnitude), not scheduler
noise.
"""

import time

import numpy as np
import pytest

from repro.comm import strategies as comm_strategies
from repro.comm.exchange import plan, random_pattern
from repro.comm.fusion import fuse
from repro.comm.topology import PodTopology

#: generous wall-time budget for planning+fusing one strategy on the fixed
#: 16-rank pattern below (vectorized planner: ~5 ms; legacy: ~70 ms)
PLAN_BUDGET_S = 2.0


def _fixed_pattern():
    rng = np.random.default_rng(1234)
    topo = PodTopology(npods=4, ppn=4)  # 16 ranks
    return random_pattern(rng, topo, local_size=16, p_connect=0.5, max_elems=8)


def test_planning_within_time_budget():
    pat = _fixed_pattern()
    for strategy in ("standard", "two_step", "three_step", "split"):
        t0 = time.perf_counter()
        fuse(plan(strategy, pat, message_cap_bytes=512))
        elapsed = time.perf_counter() - t0
        assert elapsed < PLAN_BUDGET_S, (
            f"{strategy}: planning took {elapsed:.2f}s (budget {PLAN_BUDGET_S}s); "
            "did the planner fall back to per-token Python loops?"
        )


def test_plan_cache_hits_on_second_use():
    """The module plan cache must serve repeated plans of an equal pattern."""
    pat = _fixed_pattern()
    comm_strategies.clear_caches()
    sp1 = comm_strategies.planned(pat, "two_step", message_cap_bytes=512)
    stats = comm_strategies.cache_stats()
    assert stats.plan_misses == 1 and stats.plan_hits == 0
    sp2 = comm_strategies.planned(pat, "two_step", message_cap_bytes=512)
    stats = comm_strategies.cache_stats()
    assert stats.plan_hits == 1
    assert sp2 is sp1
    # different cap is a different exchange: no false sharing
    comm_strategies.planned(pat, "two_step", message_cap_bytes=256)
    stats = comm_strategies.cache_stats()
    assert stats.plan_misses == 2 and stats.plan_hits == 1
    comm_strategies.clear_caches()


def test_plan_cache_eviction_under_many_fingerprints(monkeypatch):
    """The plan LRU must cap at PLAN_CACHE_MAX, evict oldest-first, and keep
    hot entries resident."""
    rng = np.random.default_rng(7)
    topo = PodTopology(npods=2, ppn=2)
    pats = [
        random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=2)
        for _ in range(5)
    ]
    assert len({p.fingerprint() for p in pats}) == 5
    comm_strategies.clear_caches()
    monkeypatch.setattr(comm_strategies, "PLAN_CACHE_MAX", 3)
    for p in pats:
        comm_strategies.planned(p, "two_step", message_cap_bytes=64)
    assert len(comm_strategies._PLAN_CACHE) == 3
    stats = comm_strategies.cache_stats()
    assert stats.plan_misses == 5 and stats.plan_hits == 0
    # newest three are resident...
    for p in pats[2:]:
        comm_strategies.planned(p, "two_step", message_cap_bytes=64)
    assert comm_strategies.cache_stats().plan_hits == 3
    # ...oldest two were evicted and re-plan as misses
    comm_strategies.planned(pats[0], "two_step", message_cap_bytes=64)
    stats = comm_strategies.cache_stats()
    assert stats.plan_misses == 6
    comm_strategies.clear_caches()


def test_compute_cache_eviction_under_many_fingerprints(monkeypatch):
    """The local-compute compile LRU evicts by fingerprint but never grows a
    second entry for a repeated (fingerprint, k)."""
    import jax
    from repro.sparse import spmv as spmv_mod

    mesh = jax.make_mesh((1, 1), ("pod", "local"))
    comm_strategies.clear_caches()
    monkeypatch.setattr(spmv_mod, "COMPUTE_CACHE_MAX", 4)
    for fp in ("fp0", "fp1", "fp2", "fp3", "fp4", "fp5"):
        spmv_mod._compute_program(fp, mesh, False, 4)
    assert len(spmv_mod._COMPUTE_CACHE) == 4
    stats = comm_strategies.cache_stats()
    assert stats.compute_misses == 6 and stats.compute_hits == 0
    # distinct k widths of a resident fingerprint are distinct entries ...
    spmv_mod._compute_program("fp5", mesh, False, 8)
    spmv_mod._compute_program("fp5", mesh, False, None)
    # ... repeats are hits, not rebuilds
    spmv_mod._compute_program("fp5", mesh, False, 4)
    spmv_mod._compute_program("fp5", mesh, False, 8)
    stats = comm_strategies.cache_stats()
    assert stats.compute_misses == 8 and stats.compute_hits == 2
    # evicted fingerprint re-misses
    spmv_mod._compute_program("fp0", mesh, False, 4)
    assert comm_strategies.cache_stats().compute_misses == 9
    comm_strategies.clear_caches()
    assert len(spmv_mod._COMPUTE_CACHE) == 0  # registered external cache


def test_split_cache_counts_and_clears(monkeypatch):
    """_SPLIT_CACHE must be visible to cache_stats (split_hits/split_misses),
    evict LRU-style at PLAN_CACHE_MAX, and reset under clear_caches."""
    rng = np.random.default_rng(21)
    topo = PodTopology(npods=2, ppn=2)
    pats = [
        random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=2)
        for _ in range(4)
    ]
    assert len({p.fingerprint() for p in pats}) == 4
    comm_strategies.clear_caches()
    monkeypatch.setattr(comm_strategies, "PLAN_CACHE_MAX", 3)
    for p in pats:
        comm_strategies._split_phase_cached(p)
    stats = comm_strategies.cache_stats()
    assert stats.split_misses == 4 and stats.split_hits == 0
    assert len(comm_strategies._SPLIT_CACHE) == 3
    # resident fingerprints hit; the evicted oldest re-misses
    comm_strategies._split_phase_cached(pats[-1])
    assert comm_strategies.cache_stats().split_hits == 1
    comm_strategies._split_phase_cached(pats[0])
    stats = comm_strategies.cache_stats()
    assert stats.split_misses == 5 and stats.split_hits == 1
    # the split cache never bleeds into the plan counters
    assert stats.plan_misses == 0 and stats.plan_hits == 0
    comm_strategies.clear_caches()
    stats = comm_strategies.cache_stats()
    assert stats.split_misses == 0 and stats.split_hits == 0
    assert len(comm_strategies._SPLIT_CACHE) == 0


def _flat_prims(jaxpr, out):
    for e in jaxpr.eqns:
        out[e.primitive.name] = out.get(e.primitive.name, 0) + 1
        for v in e.params.values():
            if hasattr(v, "jaxpr"):
                _flat_prims(v.jaxpr, out)
    return out


@pytest.mark.parametrize("feat", [(), (3,)])
def test_execute_scratch_is_one_fused_pad(feat):
    """The executor's ``ext = [local | buf]`` scratch must be built with a
    single fused pad -- no zeros buffer materialized and concatenated per
    call.  Pinned on a collective-free (gather-only) program so the op
    census is exact: one ``pad``, zero ``concatenate``."""
    import jax

    from repro.comm.strategies import _execute

    topo = PodTopology(npods=2, ppn=2)
    L, w_max, out_size = 4, 6, 5
    ops = (("gather", 6), ("gather", 5))
    i1 = np.zeros((1, 6), np.int32)
    i2 = np.zeros((1, 5), np.int32)
    x = np.zeros((1, L) + feat, np.float32)
    jaxpr = jax.make_jaxpr(
        lambda l, a, b: _execute(ops, topo, L, w_max, out_size, l, (a, b))
    )(x, i1, i2)
    prims = _flat_prims(jaxpr.jaxpr, {})
    assert prims.get("pad", 0) == 1, prims
    assert prims.get("concatenate", 0) == 0, prims


@pytest.mark.slow
def test_batched_plan_cache_keying_on_devices(subproc):
    """Distinct payload widths k must NOT thrash the plan/compile caches:
    one plan + one executor per pattern fingerprint, one local-compute
    compile entry per (fingerprint, k)."""
    subproc(
        """
import numpy as np
from repro.comm import strategies as S
from repro.comm.topology import PodTopology
from repro.sparse import thermal_like, build

rng = np.random.default_rng(0)
topo = PodTopology(npods=2, ppn=4)
A = thermal_like(64, rng)
S.clear_caches()
sp = build(A, topo, strategy="two_step", use_pallas=False)
s = S.cache_stats()
assert s.plan_misses == 1 and s.exec_misses == 1, s
assert s.compute_misses == 1, s  # the width=None vector program
V = rng.normal(size=(A.n, 16)).astype(np.float32).reshape(topo.nranks, -1, 16)
for k in (1, 4, 16, 4, 1):
    sp.matmat(V[:, :, :k])
s = S.cache_stats()
# one compile entry per distinct k (1, 4, 16) + the vector program; repeat
# widths are served by the instance memo and never touch the module LRU
assert s.compute_misses == 4, s
assert s.compute_hits == 0, s
# the exchange kept exactly ONE plan/executor for the fingerprint: batched
# widths specialize inside the jitted executor, not the plan cache
assert s.plan_misses == 1 and s.exec_misses == 1, s
# full rebuild for the same matrix is all hits, no recompiles
sp2 = build(A, topo, strategy="two_step", use_pallas=False)
sp2.matmat(V)
s2 = S.cache_stats()
assert s2.plan_misses == 1 and s2.exec_misses == 1, s2
assert s2.compute_misses == 4 and s2.compute_hits == 2, s2
print("BATCHED CACHE OK", s2)
""",
        devices=8,
    )


@pytest.mark.slow
def test_exchange_compile_cache_hits_on_devices(subproc):
    """Second IrregularExchange construction reuses plan AND jitted executor."""
    subproc(
        """
import time
import numpy as np
from repro.comm import strategies as S
from repro.comm.exchange import random_pattern
from repro.comm.topology import PodTopology

rng = np.random.default_rng(1234)
topo = PodTopology(npods=4, ppn=4)
pat = random_pattern(rng, topo, local_size=16, p_connect=0.5, max_elems=8)
S.clear_caches()

t0 = time.perf_counter()
ex1 = S.IrregularExchange(pat, "two_step", message_cap_bytes=512)
cold = time.perf_counter() - t0
s1 = S.cache_stats()
assert s1.plan_misses == 1 and s1.exec_misses == 1, s1
assert s1.plan_hits == 0 and s1.exec_hits == 0, s1

t0 = time.perf_counter()
ex2 = S.IrregularExchange(pat, "two_step", message_cap_bytes=512)
warm = time.perf_counter() - t0
s2 = S.cache_stats()
assert s2.plan_hits >= 1, s2
assert s2.exec_hits >= 1, s2
assert ex2._fn is ex1._fn, "jitted executor was rebuilt"

local = rng.normal(size=(topo.nranks, 16)).astype(np.float32)
ref = pat.reference(local)
H = pat.max_recv_size()
np.testing.assert_array_equal(np.asarray(ex2(local))[:, :H], ref[:, :H])
print(f"CACHE OK cold={cold*1e3:.1f}ms warm={warm*1e3:.1f}ms")
""",
        devices=16,
    )


def test_plan_cache_pressure_under_skewed_stream():
    """A Zipf-skewed fingerprint stream past capacity (the serving regime:
    few hot tenants, long churning tail) must keep the hot classes resident.
    Pins a hit-rate floor and the eviction-counter consistency invariant
    ``evictions == misses - live_entries`` (capacity never shrank)."""
    from repro.testing import make_trace

    rng = np.random.default_rng(31)
    topo = PodTopology(npods=2, ppn=2)
    pats = {
        f"p{i}": random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=2)
        for i in range(12)
    }
    assert len({p.fingerprint() for p in pats.values()}) == 12
    trace = make_trace(5, 300, sorted(pats), pattern="poisson", skew=1.4)
    comm_strategies.clear_caches()
    old = comm_strategies.PLAN_CACHE_MAX
    try:
        comm_strategies.set_cache_limits(plan=4)
        for req in trace:
            comm_strategies.planned(pats[req.fp], "two_step", message_cap_bytes=64)
        stats = comm_strategies.cache_stats()
        live = comm_strategies.cache_sizes()
        assert live["plan"] == 4  # pinned at capacity, not unbounded
        assert stats.plan_hits + stats.plan_misses == 300
        hit_rate = stats.plan_hits / 300
        assert hit_rate >= 0.5, f"hot classes not staying resident: {hit_rate:.2f}"
        assert stats.plan_evictions > 0  # the tail really churned
        assert stats.plan_evictions == stats.plan_misses - live["plan"]
    finally:
        comm_strategies.set_cache_limits(plan=old)
        comm_strategies.clear_caches()


def test_compute_cache_pressure_under_skewed_stream(monkeypatch):
    """Same pressure invariants for the registered-external compute LRU."""
    import jax

    from repro.sparse import spmv as spmv_mod
    from repro.testing import make_trace

    mesh = jax.make_mesh((1, 1), ("pod", "local"))
    comm_strategies.clear_caches()
    monkeypatch.setattr(spmv_mod, "COMPUTE_CACHE_MAX", 4)
    trace = make_trace(6, 200, [f"fp{i}" for i in range(10)], skew=1.5)
    for req in trace:
        spmv_mod._compute_program(req.fp, mesh, False, 4)
    stats = comm_strategies.cache_stats()
    assert len(spmv_mod._COMPUTE_CACHE) == 4
    assert stats.compute_hits + stats.compute_misses == 200
    assert stats.compute_hits / 200 >= 0.5
    assert stats.compute_evictions > 0
    assert stats.compute_evictions == stats.compute_misses - len(
        spmv_mod._COMPUTE_CACHE
    )
    comm_strategies.clear_caches()
    stats = comm_strategies.cache_stats()
    assert stats.compute_evictions == 0 and stats.plan_evictions == 0


def test_fused_cache_pressure_under_skewed_stream():
    """The fused whole-solve program cache under the same Zipf-skewed
    stream: PR 8's cache-pressure machinery must govern fused programs too
    -- hot solve classes stay resident, ``cache_sizes()`` reports the live
    count, ``set_cache_limits(fused=...)`` trims LRU-first immediately, and
    the eviction counters keep ``evictions == misses - live``."""
    from repro.testing import make_trace

    comm_strategies.clear_caches()
    old = comm_strategies.FUSED_CACHE_MAX
    try:
        comm_strategies.set_cache_limits(fused=4)
        trace = make_trace(7, 200, [f"fp{i}" for i in range(10)], skew=1.5)
        for req in trace:
            comm_strategies.fused_cached(("fused", "cg", req.fp), object)
        stats = comm_strategies.cache_stats()
        live = comm_strategies.cache_sizes()
        assert live["fused"] == 4  # pinned at capacity, not unbounded
        assert stats.fused_hits + stats.fused_misses == 200
        assert stats.fused_hits / 200 >= 0.5, "hot solves not staying resident"
        assert stats.fused_evictions > 0  # the tail really churned
        assert stats.fused_evictions == stats.fused_misses - live["fused"]
        # shrinking the cap mid-flight evicts LRU-first right away and the
        # counters record the trim without breaking the invariant
        caps = comm_strategies.set_cache_limits(fused=2)
        assert caps["fused"] == 2
        assert comm_strategies.cache_sizes()["fused"] == 2
        stats2 = comm_strategies.cache_stats()
        assert stats2.fused_evictions == stats.fused_evictions + 2
        assert stats2.fused_evictions == stats2.fused_misses - 2
        with pytest.raises(ValueError):
            comm_strategies.set_cache_limits(fused=0)
    finally:
        comm_strategies.set_cache_limits(fused=old)
        comm_strategies.clear_caches()
    stats = comm_strategies.cache_stats()
    assert stats.fused_evictions == 0 and stats.fused_misses == 0


def test_set_cache_limits_trims_immediately():
    """Shrinking a cap mid-flight evicts LRU-first right away (the serving
    memory-budget hook), and the eviction counters record the trim."""
    rng = np.random.default_rng(43)
    topo = PodTopology(npods=2, ppn=2)
    pats = [
        random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=2)
        for _ in range(5)
    ]
    comm_strategies.clear_caches()
    old = comm_strategies.PLAN_CACHE_MAX
    try:
        for p in pats:
            comm_strategies.planned(p, "two_step", message_cap_bytes=64)
        assert comm_strategies.cache_sizes()["plan"] == 5
        caps = comm_strategies.set_cache_limits(plan=2)
        assert caps["plan"] == 2
        assert comm_strategies.cache_sizes()["plan"] == 2
        assert comm_strategies.cache_stats().plan_evictions == 3
        # the survivors are the most recently used (LRU-first trim)
        comm_strategies.planned(pats[-1], "two_step", message_cap_bytes=64)
        comm_strategies.planned(pats[-2], "two_step", message_cap_bytes=64)
        assert comm_strategies.cache_stats().plan_hits == 2
        with pytest.raises(ValueError):
            comm_strategies.set_cache_limits(plan=0)
    finally:
        comm_strategies.set_cache_limits(plan=old)
        comm_strategies.clear_caches()


@pytest.mark.slow
def test_exchange_cache_pressure_on_devices(subproc):
    """The exchange front-door LRU under the same skewed stream: hot
    fingerprints stay resident, counters stay consistent."""
    subproc(
        """
import numpy as np
from repro.comm import strategies as S
from repro.comm.exchange import random_pattern
from repro.comm.topology import PodTopology
from repro.testing import make_trace

rng = np.random.default_rng(2)
topo = PodTopology(npods=2, ppn=2)
pats = {
    f"p{i}": random_pattern(rng, topo, local_size=4, p_connect=0.6, max_elems=2)
    for i in range(8)
}
S.clear_caches()
S.set_cache_limits(exchange=3)
trace = make_trace(9, 80, sorted(pats), skew=1.5)
for req in trace:
    S.exchange_for(pats[req.fp], "two_step", message_cap_bytes=64)
s = S.cache_stats()
live = S.cache_sizes()
assert live["exchange"] == 3, live
assert s.exchange_hits + s.exchange_misses == 80, s
assert s.exchange_hits / 80 >= 0.5, s
assert s.exchange_evictions > 0, s
assert s.exchange_evictions == s.exchange_misses - live["exchange"], s
print("EXCHANGE PRESSURE OK", s.exchange_hits, s.exchange_misses, s.exchange_evictions)
""",
        devices=4,
    )


def test_compile_cache_goes_where_the_environment_says(subproc, monkeypatch, tmp_path):
    # JAX reads JAX_COMPILATION_CACHE_DIR when it is imported, so the child
    # gets it in its environment; the helper must then set no other directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = subproc(
        """
import os
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache

want = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert use_compile_cache() == want
assert jax.config.jax_compilation_cache_dir == want
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
print("CACHE", sorted(os.listdir(want)))
""",
        devices=1,
    )
    assert "jit__lambda" in out
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())


def test_compile_cache_default_is_a_fixed_ignored_dir(monkeypatch):
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
