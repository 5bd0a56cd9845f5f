#!/usr/bin/env python3
"""Run the distributed SpMV and the CG solve compiled on a TPU, and check them.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip: the 1,048,576-row 5-point stencil (``thermal_like``, made SPD by
``spd_system``, drawn from ``--seed``) on one rank.  The barrier SpMV is
checked against the CSR product in numpy, the overlapped SpMV must equal it
bit for bit, ``matmat`` at k = 8 is checked against ``reference_mm``, and
``fused_cg`` must converge with the host loop's iteration count and a true
residual of at most 1e-5.

Four chips (``--chips 4``, and no other phase): ``PodTopology(npods=2,
ppn=2)``, whose two pods are virtual on one host (every hop runs over ICI).
For the stencil and for ``random_block(262_144, 16 / 262_144)`` every
exchange strategy runs barrier and overlapped; all eight results must be
bitwise equal and match the reference.  The ELL blocks and plan arrays must
sit on four distinct devices, and ``fused_cg`` with ``two_step`` on the
stencil must converge with the host loop's iteration count.

The script runs in one process and starts none.  Without a TPU it fails
before printing any result.  It exits non-zero on any failed check; the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

STENCIL_ROWS = 1_048_576
RANDOM_ROWS = 262_144
MM_WIDTH = 8
STRATEGIES = ("standard", "two_step", "three_step", "split")
SPMV_CALLS = 20
TOL = 1e-6


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


def log(kind: str, **fields) -> None:
    print(f"[{kind}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def median_us(fn, calls: int = SPMV_CALLS) -> float:
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def stencil(rows: int, rng: np.random.Generator):
    from repro.solve import spd_system
    from repro.sparse import thermal_like

    return spd_system(thermal_like(rows, rng))


def check_solve(kind: str, op, A, rng: np.random.Generator) -> None:
    """Host-loop CG and the fused solve on ``op``: both converge, with the
    same iteration count, to a true residual of at most 1e-5."""
    from repro.solve import cg, fused_cg

    g, L = op.topo.nranks, op.rows_per_rank
    b = rng.standard_normal(A.n).astype(np.float32)
    t0 = time.perf_counter()
    host = cg(op, b.reshape(g, L), tol=TOL)
    host_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused = fused_cg(op, b.reshape(g, L), tol=TOL)
    fused_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused = fused_cg(op, b.reshape(g, L), tol=TOL)
    fused_s = time.perf_counter() - t0
    x = np.asarray(fused.x, np.float64).reshape(-1)
    true_res = rel_err(A.spmv(x), b)
    log(kind, strategy=op.strategy, host_cg_s=host_s, host_iters=host.iterations,
        fused_cg_first_s=fused_first_s, fused_cg_s=fused_s,
        fused_iters=fused.iterations, fused_status=fused.status, true_residual=true_res)
    require(host.status == "converged", f"host cg status {host.status}")
    require(fused.status == "converged", f"fused cg status {fused.status}")
    require(fused.iterations == host.iterations,
            f"fused cg took {fused.iterations} iterations, host loop {host.iterations}")
    require(true_res <= 1e-5, f"true residual {true_res} > 1e-5")


def one_chip(kind: str, rows: int, seed: int) -> int:
    """Run the one-chip phases; return the number of devices the mesh used."""
    from repro.comm.topology import PodTopology, shard_ranks
    from repro.sparse import reference, reference_mm
    from repro.sparse.partition import partition_csr
    from repro.sparse.spmv import DistributedSpMV

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    A = stencil(rows, rng)
    part = partition_csr(A, PodTopology(npods=1, ppn=1))
    sp = DistributedSpMV(part, strategy="standard")
    ov = DistributedSpMV(part, strategy="standard", overlap=True)
    v = rng.standard_normal(A.n).astype(np.float32)
    V = rng.standard_normal((A.n, MM_WIDTH)).astype(np.float32)
    vr = shard_ranks(v.reshape(1, -1), sp.mesh)
    Vr = shard_ranks(V.reshape(1, -1, MM_WIDTH), sp.mesh)
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    w = sp(vr).block_until_ready()
    first_s = time.perf_counter() - t0
    # the compiled local-compute program must hold the Mosaic kernel
    hlo = sp._compute.lower(vr, sp.halo(vr), *sp._blocks).compile().as_text()
    require("tpu_custom_call" in hlo, "compiled SpMV program has no tpu_custom_call")
    spmv_us = median_us(lambda: sp(vr))
    log(kind, rows=A.n, nnz=A.nnz, setup_s=setup_s, first_spmv_s=first_s,
        spmv_us_median=spmv_us, spmv_calls=SPMV_CALLS)

    w = np.asarray(w).reshape(-1)
    err = rel_err(w, reference(A, v))
    require(err <= 1e-5, f"SpMV relative error {err} > 1e-5")
    require(np.array_equal(np.asarray(ov(vr)).reshape(-1), w), "overlap != barrier bitwise")

    W = np.asarray(sp.matmat(Vr)).reshape(A.n, MM_WIDTH)
    mm_err = rel_err(W, reference_mm(A, V))
    spmm_us = median_us(lambda: sp.matmat(Vr))
    log(kind, spmv_rel_err=err, spmm_k=MM_WIDTH, spmm_rows=A.n, spmm_rel_err=mm_err,
        spmm_us_median=spmm_us)
    require(mm_err <= 1e-5, f"SpMM relative error {mm_err} > 1e-5")

    check_solve(kind, sp, A, rng)
    return sp.mesh.devices.size


def _on_distinct_devices(arrays, n: int) -> bool:
    return all(
        len(a.sharding.device_set) == n
        and all(s.data.shape[0] == 1 for s in a.addressable_shards)
        for a in arrays
    )


def four_chips(kind: str, stencil_rows: int, random_rows: int, seed: int) -> int:
    """Run the four-chip phases; return the number of devices the mesh used."""
    from repro.comm.topology import PodTopology, shard_ranks
    from repro.sparse import random_block, reference
    from repro.sparse.partition import partition_csr
    from repro.sparse.spmv import DistributedSpMV

    rng = np.random.default_rng(seed)
    topo = PodTopology(npods=2, ppn=2)
    for name, make in (
        ("stencil", lambda: stencil(stencil_rows, rng)),
        ("random_block", lambda: random_block(random_rows, 16 / random_rows, rng)),
    ):
        t0 = time.perf_counter()
        A = make()
        part = partition_csr(A, topo)
        v = rng.standard_normal(A.n).astype(np.float32)
        want = reference(A, v)
        log(kind, matrix=name, rows=A.n, nnz=A.nnz, halo=part.halo_width,
            setup_s=time.perf_counter() - t0)
        outs = []
        for strategy in STRATEGIES:
            for overlap in (False, True):
                t0 = time.perf_counter()
                op = DistributedSpMV(part, strategy=strategy, overlap=overlap)
                vr = shard_ranks(v.reshape(topo.nranks, -1), op.mesh)
                w = op(vr).block_until_ready()
                first_s = time.perf_counter() - t0
                plan = op.exchange.traceable().plan_arrays
                require(_on_distinct_devices(op._blocks + plan, topo.nranks),
                        f"{name}/{strategy}: operands not one rank per device")
                w = np.asarray(w).reshape(-1)
                err = rel_err(w, want)
                log(kind, matrix=name, strategy=strategy, overlap=overlap,
                    build_and_first_spmv_s=first_s, spmv_us_median=median_us(lambda: op(vr)),
                    rel_err=err)
                require(err <= 1e-5, f"{name}/{strategy}/overlap={overlap}: error {err}")
                outs.append(w)
        require(all(np.array_equal(o, outs[0]) for o in outs),
                f"{name}: strategies / overlap disagree bitwise")
        if name == "stencil":
            check_solve(kind, DistributedSpMV(part, strategy="two_step"), A, rng)
    return topo.nranks


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} TPUs, found {len(devices)}")

    from repro.launch.compile_cache import use_compile_cache

    log("cache", dir=use_compile_cache())
    kind = devices[0].device_kind
    if args.chips == 1:
        count = one_chip(kind, STENCIL_ROWS, args.seed)
    else:
        count = four_chips(kind, STENCIL_ROWS, RANDOM_ROWS, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": int(count)}}))


if __name__ == "__main__":
    main()
