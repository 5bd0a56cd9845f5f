"""The roofline's counts against counts made by hand."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import roofline
from bench.run import ROOT, load_module
from bench.csr import from_coo
from bench.matrices import stencil5_spd
from bench.peaks import PEAKS, peaks_for


def test_stencil_3x3_counts():
    # 3 x 3 grid: 9 diagonal entries, 12 grid edges each stored twice
    A = stencil5_spd.generate({"grid_side": 3, "spd_shift": 1.0}, seed=0)
    assert (A.n, A.nnz) == (9, 9 + 24)
    (one,) = roofline.rank_work(A, 1)
    assert (one.rows, one.nnz, one.halo) == (9, 33, 0)
    assert one.bytes == 4 * (33 + 9 + 0 + 9)
    assert one.flops == 66
    flops, nbytes = roofline.cg_iteration_work(A.n, A.nnz)
    assert nbytes == 4 * (33 + 6 * 9)
    assert flops == 2 * 33 + 4 * 9 + 6 * 9


def test_random_4x4_halo_by_hand():
    # rows 0-1 on rank 0, rows 2-3 on rank 1
    rows = [0, 0, 0, 1, 1, 2, 2, 3, 3, 3]
    cols = [0, 2, 3, 1, 3, 0, 2, 3, 0, 1]
    A = from_coo(4, rows, cols, np.ones(10))
    r0, r1 = roofline.rank_work(A, 2)
    assert (r0.rows, r0.nnz, r0.halo) == (2, 5, 2)  # reads columns 2 and 3
    assert (r1.rows, r1.nnz, r1.halo) == (2, 5, 2)  # reads columns 0 and 1
    assert r0.bytes == 4 * (5 + 2 + 2 + 2)


def test_cg_roofline_reads_each_chip_against_its_own_rows():
    # 4 x 4 grid over 4 ranks, one grid row a rank: the end rows have 4
    # diagonal + 6 in-row + 4 cross-row entries and read 4 halo values, the
    # middle rows 4 + 6 + 8 and read 8
    A = stencil5_spd.generate({"grid_side": 4, "spd_shift": 1.0}, seed=0)
    work = roofline.rank_work(A, 4)
    assert [(w.rows, w.nnz, w.halo) for w in work] == [
        (4, 14, 4), (4, 18, 8), (4, 18, 8), (4, 14, 4)]
    devices = [f"/device:TPU:{i}" for i in range(4)]
    busy = [1e-6, 2e-6, 3e-6, 4e-6]
    run = SimpleNamespace(
        window=SimpleNamespace(counters={"iterations": [10, 12]}),
        work=work, rank_devices=devices, peaks=peaks_for("TPU v5 lite"),
        trace=SimpleNamespace(busy_s=dict(zip(devices, busy))))
    by_hand = [4 * (14 + 4 + 6 * 4), 4 * (18 + 8 + 6 * 4), 4 * (18 + 8 + 6 * 4),
               4 * (14 + 4 + 6 * 4)]
    want = sum(100.0 * b / 819e9 * 22 / t for b, t in zip(by_hand, busy)) / 4
    got = load_module(ROOT / "bench/metrics/cg_roofline.py").read(run)
    assert got == pytest.approx(want)


def test_rank_work_rejects_uneven_split():
    A = from_coo(3, [0, 1, 2], [0, 1, 2], np.ones(3))
    with pytest.raises(ValueError):
        roofline.rank_work(A, 2)


def test_floor_is_memory_bound_for_spmv():
    peaks = peaks_for("TPU v5 lite")
    t, bound = roofline.floor_seconds(2e6, 5.8e6, peaks)
    assert bound == "memory"
    assert t == pytest.approx(5.8e6 / 819e9)


def test_unknown_device_kind_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
