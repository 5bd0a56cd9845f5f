"""Distributed Krylov solve: the iterative workload the paper's models
amortize over.

1. Build an SPD system with thermal2-like communication structure and ask
   the iteration-amortized advisor (`repro.core.advise_solver`) which
   strategy wins a whole solve -- setup cost paid once, per-iteration
   exchange + hierarchical-reduction cost multiplied by the iteration count.
   Note the flip: a 1-iteration "solve" favours standard communication
   (no communicator construction), a real solve favours the node-aware
   winner.
2. Solve with CG on the jax-free numpy executor (`repro.solve.NumpySpMV`)
   under every strategy, barrier and split-phase: one cached exchange plan
   serves all iterations (shown via `repro.comm.cache_stats()`) and the
   residual histories are bitwise identical across all configurations.
3. Re-run on real devices (`repro.sparse.DistributedSpMV`, 8 forced host
   chips) with dot products through the node-aware hierarchical collectives
   (`repro.solve.DeviceReductions`), including an int8-compressed
   inter-pod reduction variant.
4. With ``--fused``: compare the host-driven loop against the fused
   whole-solve program (`repro.solve.fused_cg`) -- one jitted
   ``lax.while_loop`` per solve, cached in the fused-program LRU -- and ask
   the advisor's `LaunchModel` accounting (`advise_solver(fused="auto")`)
   at which horizon the one-time trace cost beats the per-iteration host
   dispatches.

    PYTHONPATH=src python examples/krylov_solve.py [--fused]
"""

import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    from repro.comm import cache_stats, clear_caches
    from repro.comm.topology import PodTopology
    from repro.core import advise_solver, figure43_pattern
    from repro.solve import NumpySpMV, REDUCTIONS_PER_ITER, cg, spd_system
    from repro.sparse import partition_csr, thermal_like

    fused = "--fused" in sys.argv[1:]
    rng = np.random.default_rng(0)
    topo = PodTopology(npods=2, ppn=4)
    A = spd_system(thermal_like(1024, rng))
    part = partition_csr(A, topo)
    pattern = part.pattern.to_comm_pattern()
    b = rng.normal(size=(topo.nranks, part.rows_per_rank))

    if os.environ.get("_KS_CHILD") == "1":
        # the 8-device re-launch only runs the device solves (steps 3/4)
        _device_execution(topo, part, b, fused=os.environ.get("_KS_FUSED") == "1")
        return

    print(f"SPD system n={A.n} nnz={A.nnz} on {topo.nranks} ranks\n")

    # 1. iteration-amortized strategy selection.  On the paper's flagship
    #    pattern (256 x 2 KiB messages to 16 nodes, Fig 4.3) the winner
    #    FLIPS with the horizon: standard wins a 1-iteration "solve" (no
    #    communicator construction), 2-Step wins once its setup amortizes.
    flagship = figure43_pattern(2048, 256, 16)
    for iters in (1, 200):
        adv = advise_solver(
            flagship, iters, machine="lassen",
            reductions_per_iter=REDUCTIONS_PER_ITER["cg"],
        )
        print(f"amortized advisor on the Fig 4.3 pattern, iters={iters}:")
        print(adv.table())
        print(f"-> best for a {iters}-iteration solve: {adv.best.key}\n")
    #    ... while this small stencil system is latency-bound at every
    #    horizon: node-aware setup never pays for itself (also the paper's
    #    conclusion for small per-message volumes).
    adv = advise_solver(pattern, 200, machine="tpu_v5e_pod",
                        reductions_per_iter=REDUCTIONS_PER_ITER["cg"])
    print(f"this matrix's own pattern, iters=200 -> {adv.best.key} "
          f"(latency-bound: no flip)\n")

    # 2. CG on the numpy executor: every strategy, barrier + split-phase
    clear_caches()
    histories = {}
    for strategy in ("standard", "two_step", "three_step", "split"):
        for overlap in (False, True):
            op = NumpySpMV(part, strategy=strategy, overlap=overlap)
            res = cg(op, b, tol=1e-6)
            histories[(strategy, overlap)] = res.residuals
            assert res.converged
    ref = histories[("standard", False)]
    assert all(h == ref for h in histories.values())
    s = cache_stats()
    print(f"numpy executor: {len(histories)} strategy/overlap configs, "
          f"all converged in {len(ref) - 1} iterations with bitwise-identical "
          f"residual histories")
    print(f"plan cache over all solves: {s.plan_misses} misses "
          f"(one per distinct sub-pattern), {s.plan_hits} hits; "
          f"split decompositions: {s.split_misses} miss, {s.split_hits} hits\n")

    if fused:
        # 2b. where does the fused front-end win?  The LaunchModel charges
        #     the host loop t_launch per dispatch and the fused program one
        #     t_trace up front; the ranking flips to +fused once the trace
        #     amortizes (~t_trace / (launches_per_iter * t_launch) iters).
        for iters in (50, 400):
            adv = advise_solver(
                flagship, iters, machine="lassen", fused="auto",
                reductions_per_iter=REDUCTIONS_PER_ITER["cg"],
            )
            print(f"fused-aware advisor, iters={iters} -> {adv.best.key}")
        print()

    # 3. device executor + hierarchical reductions (8 forced host chips;
    #    XLA_FLAGS must be set before jax import, hence the re-launch)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # never compete for an accelerator
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["_KS_CHILD"] = "1"
    if fused:
        env["_KS_FUSED"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(sys.path)
    print("re-running the solve on 8 host devices...")
    out = subprocess.run([sys.executable, __file__], env=env,
                         capture_output=True, text=True)
    start = out.stdout.find("DEVICE EXECUTION")
    print(out.stdout[start:] if start >= 0 else out.stderr[-2000:])


def _device_execution(topo, part, b, fused=False) -> None:
    from repro.comm import Compressor, cache_stats
    from repro.solve import DeviceReductions, cg, fused_cg
    from repro.sparse import DistributedSpMV

    print("DEVICE EXECUTION")
    bf = b.astype(np.float32)
    red = DeviceReductions(topo)
    for strategy, overlap in (("two_step", False), ("two_step", True)):
        op = DistributedSpMV(part, strategy=strategy, use_pallas=False,
                             overlap=overlap)
        res = cg(op, bf, tol=1e-6, reductions=red)
        mode = "overlap" if overlap else "barrier"
        print(f"  {strategy:9s} {mode:8s} converged={res.converged} "
              f"iters={res.iterations} relres={res.final_residual:.2e}")
    comp = DeviceReductions(topo, compressor=Compressor())
    res = cg(DistributedSpMV(part, strategy="two_step", use_pallas=False),
             bf, tol=1e-4, maxiter=200, reductions=comp)
    print(f"  two_step  int8-compressed inter-pod reductions: "
          f"converged={res.converged} iters={res.iterations} "
          f"relres={res.final_residual:.2e}")
    if not fused:
        return
    # 4. fused whole-solve program: same SolveResult contract, ONE compiled
    #    lax.while_loop instead of per-iteration host dispatches
    op = DistributedSpMV(part, strategy="two_step", use_pallas=False)
    host = cg(op, bf, tol=1e-6, reductions=red)
    fres = fused_cg(op, bf, tol=1e-6)
    s = cache_stats()
    drift = max(
        abs(a - c) / max(abs(c), 1e-30)
        for a, c in zip(fres.residuals, host.residuals)
    )
    print(f"  two_step  fused whole-solve: converged={fres.converged} "
          f"iters={fres.iterations} (host {host.iterations}), "
          f"history drift {drift:.1e}, "
          f"{s.fused_misses} program compile / {s.fused_hits} cache hits")


if __name__ == "__main__":
    main()
