"""The scoped reduction of a profiler trace (``bench/scopes.py``): program
scopes, the host-device clock offset, idle gaps by ``bench.``/``repro.``
span, and the per-layer quantities that read scopes."""

from pathlib import Path

import pytest

from bench import scopes, trace

DATA = Path(__file__).parent / "data"
GATHER = str(DATA / "v5e_gather.xplane.pb")
TPU0 = "/device:TPU:0"


def test_innermost_scope():
    assert scopes.innermost_scope(
        "jit(program)/shard_map/while/body/solve.update/spmv/spmv.diag/spmv.gather/gather:"
    ) == "spmv.gather"
    assert scopes.innermost_scope("jit(f)/exchange/exchange.a2a_pod/exchange.codec/abs") == (
        "exchange.codec")
    assert scopes.innermost_scope("jit(f)/exchange/pad:") == "exchange"
    assert scopes.innermost_scope("jit(<lambda>)/gather:") is None
    assert scopes.innermost_scope("") is None
    # a name that only starts like a scope is none
    assert scopes.innermost_scope("jit(f)/spmv_ell/mul") is None


def test_clock_offset_on_the_recorded_gather_trace():
    """The device's three module runs start 1,420, 1,399 and 1,426 us before
    the host's launches: the offset is the largest, and after it no run
    starts before its launch."""
    scoped = scopes.read_scoped(GATHER)
    assert sorted(r for r, _ in scoped.launches) == [6, 7, 8]
    offset = scopes.clock_offset(scoped, TPU0)
    assert offset == pytest.approx(1425.516e-6, abs=1e-9)
    launches = {run: t for (run, _), t in scoped.launches.items()}
    leads = sorted(launches[run] - start for run, start, _ in scoped.modules[TPU0])
    assert leads == pytest.approx([1398.676e-6, 1420.396e-6, 1425.516e-6], abs=1e-9)
    assert all(start + offset >= launches[run] - 1e-12
               for run, start, _ in scoped.modules[TPU0])
    assert scopes.clock_offset(scoped, "/device:TPU:1") is None


def test_reduce_trace_is_unchanged_on_the_recorded_gather_trace():
    """The benchmark's own reduction reads exactly what it read before the
    program had scopes."""
    s = trace.reduce_trace(GATHER, ("bench.product", "bench.sync"), [TPU0])
    exact = pytest.approx  # to the last bits of the float arithmetic
    assert s.window_s == exact(0.015984479, rel=1e-12)
    assert s.busy_s == {TPU0: exact(13.093e-6, rel=1e-12)}
    assert s.op_s == exact({"copy-start": 39e-9, "fusion.1": 1410e-9, "copy-done": 9e-9,
                            "fusion": 9857e-9, "multiply_reduce_fusion": 1778e-9}, rel=1e-9)
    assert s.gap_s == exact({"bench.product": 2.802537e-3, "bench.sync": 10.986748e-3,
                             trace.NO_SPAN: 2.182101e-3}, rel=1e-9)


def test_scoped_reduction_of_an_unscoped_program():
    """The recorded gather program has no program scope: every operation is
    unscoped, and the metrics that read scopes read nothing."""
    s = scopes.reduce_scoped(GATHER, ("bench.product", "bench.sync"), [TPU0])
    assert s.offset_s[TPU0] == pytest.approx(1425.516e-6, abs=1e-9)
    assert s.scopes[TPU0] == {} and s.scoped_share(TPU0) == 0.0
    assert {op for op, sc in s.op_s} == {"copy-start", "copy-done", "fusion", "fusion.1",
                                         "multiply_reduce_fusion"}
    assert all(sc is None for _, sc in s.op_s)
    # the shift moves the operations, not the window: all of them are still
    # in it, and busy plus idle fill it
    assert s.busy_s[TPU0] == pytest.approx(1.3093e-05, rel=1e-3)
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s[TPU0])
    assert scopes.exchange_device_us(s, {"products": 3}) is None
    assert scopes.gather_us(s, {"products": 3}) is None
    assert scopes.reduce_scoped(GATHER, ("bench.solve",), [TPU0]) is None
    assert scopes.reduce_scoped(GATHER, ("bench.product",), ["/device:TPU:1"]) is None


def by_hand() -> scopes.ScopedTrace:
    """Two devices; TPU:1's clock runs 1.0 s early against the host."""
    return scopes.ScopedTrace(
        ops={
            "/device:TPU:0": [
                ("while.1", 1.0, 9.0, None),  # holds the two below
                ("fusion.1", 1.0, 4.0, "spmv.gather"),
                ("fusion.2", 4.0, 5.0, "exchange.gather"),
                ("copy", 6.0, 7.0, None),
            ],
            "/device:TPU:1": [
                ("fusion.1", 0.0, 2.0, "spmv.gather"),
                ("all-to-all", 2.0, 3.0, "exchange.a2a_pod"),
                ("convert", 3.0, 3.5, "exchange.codec"),
            ],
        },
        modules={"/device:TPU:0": [(7, 1.0, 9.0)], "/device:TPU:1": [(7, 0.0, 3.5)]},
        launches={(7, 0): 0.5, (7, 1): 1.0},
        spans=[("bench.product", 0.0, 10.0), ("repro.spmv", 0.0, 1.0),
               ("repro.exchange", 0.4, 0.9), ("bench.sync", 10.0, 12.0)],
    )


def test_summarize_scoped_by_hand():
    scoped = by_hand()
    assert scopes.clock_offset(scoped, "/device:TPU:0") == -0.5
    assert scopes.clock_offset(scoped, "/device:TPU:1") == 1.0
    s = scopes.summarize_scoped(scoped, (0.0, 12.0), list(scoped.ops))
    # TPU:0 shifted 0.5 s earlier: busy 0.5-8.5 (the while loop holds it)
    assert s.busy_s["/device:TPU:0"] == pytest.approx(8.0)
    assert s.scope_s("/device:TPU:0") == pytest.approx(
        {"spmv.gather": 3.0, "exchange.gather": 1.0})
    assert s.scoped_share("/device:TPU:0") == pytest.approx(4.0 / 8.0)
    # TPU:1 shifted 1 s later: 1-4.5, all of it scoped
    assert s.busy_s["/device:TPU:1"] == pytest.approx(3.5)
    assert s.scoped_share("/device:TPU:1") == pytest.approx(1.0)
    assert s.time_under("/device:TPU:1", scopes.is_exchange) == pytest.approx(1.5)
    assert s.op_s[("copy", None)] == pytest.approx(1.0)
    assert ("while.1", None) not in s.op_s
    # gaps: TPU:0 0-0.5 (innermost at its middle, 0.25: repro.spmv) and
    # 8.5-12 (10.25: bench.sync); TPU:1 0-1 (0.5: repro.exchange) and 4.5-12
    # (8.25: bench.product)
    assert s.gap_s == pytest.approx({"repro.spmv": 0.5, "bench.sync": 3.5,
                                     "repro.exchange": 1.0, "bench.product": 7.5})
    fields = s.log_fields()
    assert fields["clock_offset_us[/device:TPU:1]"] == pytest.approx(1e6)
    assert fields["scoped_pct[/device:TPU:0]"] == pytest.approx(50.0)
    # per product, mean over the two chips
    assert scopes.exchange_device_us(s, {"products": 2}) == pytest.approx(
        1e6 * (1.0 + 1.5) / 2 / 2)
    assert scopes.gather_us(s, {"products": 2}) == pytest.approx(1e6 * (3.0 + 2.0) / 2 / 2)
    # a solve loop counts iterations
    assert scopes.gather_us(s, {"solves": 1, "iterations": [4, 6]}) == pytest.approx(
        1e6 * 2.5 / 10)
    assert scopes.exchange_device_us(s, {"solves": 1}) is None


def test_window_of_matches_reduce_trace():
    ops, spans = trace.read_xspace(GATHER)
    w = scopes.window_of(scopes.read_scoped(GATHER).spans, ("bench.product", "bench.sync"))
    assert w[1] - w[0] == pytest.approx(
        trace.reduce_trace(GATHER, ("bench.product", "bench.sync"), [TPU0]).window_s, abs=1e-9)
    assert scopes.window_of(spans, ("bench.solve",)) is None


def test_scoped_run_on_cpu_reads_nothing_and_restores_the_harness(tmp_path):
    """The traced run with the scoped reduction beside it, on the CPU: the
    cell's own per-layer metrics as ``bench/run.py --trace 1`` reports them,
    no TPU plane so nothing by scope, and the harness as it was after."""
    from bench import run
    from bench.tests import tinyroot

    root = tinyroot.make(tmp_path)
    before = (trace.reduce_trace, run.load_loop)
    result = scopes.run_scoped(root, "stencil2d-cg", seed=2**31 + 7, seconds=0.3,
                               device_kind="TPU v5 lite")
    assert (trace.reduce_trace, run.load_loop) == before
    assert result["correct"]
    assert set(result["metrics"]) == {"partition_s", "cg_iters"}
    assert "scopes" not in result
    got = result["scoped_metrics"]
    assert got["exchange_device_us"] is None and got["gather_us"] is None
    assert got["per_call_host_us"] > 0


SCOPED = str(DATA / "v5e_scoped.xplane.pb")
SCOPED_WINDOW = ("bench.solve", "bench.product", "bench.sync")
CHIPS = [f"/device:TPU:{i}" for i in range(4)]
PROGRAM_SCOPES = {
    "exchange", "exchange.gather", "exchange.a2a_local", "exchange.a2a_pod",
    "exchange.permute", "exchange.codec", "spmv", "spmv.diag", "spmv.off", "spmv.gather",
    "spmv.layout", "spmv.kernel", "solve.reduce", "solve.update",
}


def test_recorded_scoped_trace_carries_every_scope():
    """Recorded on one host of four v5e (``record_scoped_trace.py``): a
    fused CG on one chip and barrier SpMVs on one and on four chips."""
    scoped = scopes.read_scoped(SCOPED)
    assert sorted(scoped.ops) == CHIPS
    assert scoped.scopes_seen == PROGRAM_SCOPES
    names = {s[0] for s in scoped.spans}
    assert {"repro.spmv", "repro.exchange", "repro.solve.upload", "repro.solve.loop",
            "repro.solve.readback", "repro.solve.download"} <= names
    s = scopes.reduce_scoped(SCOPED, SCOPED_WINDOW, CHIPS)
    for dev in CHIPS:
        assert s.scoped_share(dev) >= 0.95, (dev, s.scoped_share(dev))
        # after the shift no module run starts before its host launch
        off = s.offset_s[dev]
        assert off is not None
        launches = {run: t for (run, o), t in scoped.launches.items()
                    if o == scopes.device_ordinal(dev)}
        assert all(start + off >= launches[run] - 1e-12
                   for run, start, _ in scoped.modules[dev] if run in launches)
    assert any(label.startswith("repro.") for label in s.gap_s)


def test_scoped_metrics_on_the_recorded_trace():
    """The loop's counters for the window: one solve (its iterations) and
    3 products on each of the three operators; per product the exchange and
    the gather read a device time, which the chips that ran it bound."""
    s = scopes.reduce_scoped(SCOPED, SCOPED_WINDOW, CHIPS)
    exchange = scopes.exchange_device_us(s, {"products": 9})
    gather = scopes.gather_us(s, {"products": 9})
    assert 0 < exchange and 0 < gather
    busy_us = 1e6 * sum(s.busy_s.values()) / len(s.busy_s) / 9
    assert exchange + gather <= busy_us
    per_iteration = scopes.gather_us(s, {"solves": 1, "iterations": [1]})
    assert per_iteration == pytest.approx(9 * gather)
