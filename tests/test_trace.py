"""The program's spans and scopes (``repro.trace``) and the fused whole-solve
cache serving each operator its own matrix."""

import contextlib

import jax
import jax.monitoring
import numpy as np
import pytest

from repro import comm, trace
from repro.comm.topology import PodTopology, shard_ranks
from repro.solve import fused_cg, spd_system
from repro.sparse import matrices, partition, spmv


@contextlib.contextmanager
def durations():
    """``{event: [seconds, ...]}`` of every ``/repro/`` duration event
    reported while open."""
    got: dict = {}

    def on(event, seconds, **_):
        if event.startswith(trace.EVENT_PREFIX):
            got.setdefault(event, []).append(seconds)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def stencil(side: int, seed: int) -> matrices.CSRMatrix:
    """An SPD 5-point stencil: the sparsity is fixed by ``side``, the values
    by ``seed``."""
    return spd_system(matrices.thermal_like(side * side, np.random.default_rng(seed)))


def test_span_reports_its_duration():
    with durations() as got:
        with trace.span("probe"):
            pass
        with pytest.raises(KeyError):
            with trace.span("probe"):
                raise KeyError("the body's error passes through")
    assert list(got) == ["/repro/probe"]
    assert len(got["/repro/probe"]) == 2 and min(got["/repro/probe"]) >= 0.0


def test_partition_reports_each_host_loop():
    A = stencil(16, 0)
    with durations() as got:
        partition.partition_csr(A, PodTopology(1, 2))
    loops = ["needs", "pattern", "widths", "fill"]
    assert set(got) == {"/repro/partition"} | {f"/repro/partition.{n}" for n in loops}
    assert all(len(v) == 1 for v in got.values())
    inner = sum(got[f"/repro/partition.{n}"][0] for n in loops)
    assert inner <= got["/repro/partition"][0]


def test_build_and_product_spans():
    comm.clear_caches()
    part = partition.partition_csr(stencil(16, 1), PodTopology(1, 1))
    with durations() as got:
        op = spmv.DistributedSpMV(part, strategy="auto")
    assert set(got) == {"/repro/build", "/repro/build.advise", "/repro/build.place"}
    v = shard_ranks(np.ones((1, part.rows_per_rank), np.float32), op.mesh)
    with durations() as got:
        op(v).block_until_ready()
        op.matmat(v[:, :, None]).block_until_ready()
    # one span for the product and one for its exchange, per call
    assert {k: len(s) for k, s in got.items()} == {"/repro/spmv": 2, "/repro/exchange": 2}


def test_fused_solve_spans():
    comm.clear_caches()
    A = stencil(16, 2)
    op = spmv.DistributedSpMV(partition.partition_csr(A, PodTopology(1, 1)),
                              strategy="standard")
    b = np.random.default_rng(3).standard_normal((1, A.n)).astype(np.float32)
    with durations() as got:
        res = fused_cg(op, b, tol=1e-6, maxiter=100)
    assert res.converged and res.restarts == 0
    phases = ["upload", "loop", "readback", "download"]
    assert {k: len(s) for k, s in got.items()} == {f"/repro/solve.{p}": 1 for p in phases}


def test_fused_cache_serves_each_operator_its_own_matrix():
    """Two operators of one sparsity with other values share one compiled
    whole-solve program (one miss, one hit), and each solves its own
    system: the cached program once ran on the first operator's blocks."""
    comm.clear_caches()
    topo = PodTopology(1, 1)
    residuals = []
    for seed in (2147483655, 5):
        A = stencil(64, seed)
        op = spmv.DistributedSpMV(partition.partition_csr(A, topo), strategy="standard")
        b = np.random.default_rng(seed).standard_normal(A.n).astype(np.float32)
        res = fused_cg(op, b.reshape(1, -1), tol=1e-6, maxiter=500)
        assert res.converged
        x = res.x.reshape(-1).astype(np.float64)
        residuals.append(np.linalg.norm(b - A.spmv(x)) / np.linalg.norm(b))
    assert max(residuals) < 1e-5, residuals
    s = comm.cache_stats()
    assert (s.fused_misses, s.fused_hits) == (1, 1), s


SCOPES_IN_PROGRAMS = """
import re

import numpy as np

from repro.comm.topology import PodTopology, shard_ranks
from repro.solve.fused import _fused_entry, _limits
from repro.sparse import matrices, partition, spmv

def scopes(lowered):
    text = lowered.as_text(debug_info=True)
    # a scope is one component of an operation's name-stack path (a nested
    # jit's operations are named from its own root)
    return set(re.findall(r'["/]((?:exchange|spmv|solve)\\.[a-z0-9_]+)(?=/)', text))

A = matrices.random_block(256, 16 / 256, np.random.default_rng(0))
part = partition.partition_csr(A, PodTopology(2, 2))
v = None
found = {}
for strategy, wire in (("three_step", "none"), ("two_step", "int8")):
    op = spmv.DistributedSpMV(part, strategy=strategy, wire=wire)
    if v is None:
        v = shard_ranks(np.ones((4, part.rows_per_rank), np.float32), op.mesh)
    halo = op.exchange(v)
    found["compute"] = scopes(op._compute.lower(v, halo, *op._blocks))
    found.setdefault("exchange", set()).update(
        scopes(op.exchange._fn.lower(v, *op.exchange._arrays)))
    fn, top = _fused_entry(op, "cg", 20, np.dtype(np.float32), None)
    found["fused"] = scopes(fn.lower(v, v, *_limits(top, 1e-6, 20, np.float32),
                                     *top.operands))
for name, got in sorted(found.items()):
    print(name, ",".join(sorted(got)))
"""


def test_lowered_programs_carry_the_device_scopes(subproc):
    out = dict(line.split(" ", 1) for line in subproc(SCOPES_IN_PROGRAMS, devices=4)
               .strip().splitlines())
    got = {name: set(names.split(",")) for name, names in out.items()}
    spmv_scopes = {"spmv.diag", "spmv.off", "spmv.gather", "spmv.layout", "spmv.kernel"}
    exchange_scopes = {"exchange.gather", "exchange.a2a_local", "exchange.a2a_pod",
                       "exchange.permute", "exchange.codec"}
    assert got["compute"] == spmv_scopes
    assert got["exchange"] == exchange_scopes
    # two_step's fused solve: its exchange has no permute stage
    assert got["fused"] == (spmv_scopes | exchange_scopes | {"solve.reduce", "solve.update"}
                            ) - {"exchange.permute"}
