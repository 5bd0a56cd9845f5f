"""Benchmark entry point: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run params     # one section
    PYTHONPATH=src python -m benchmarks.run --smoke    # cheap smoke pass

Sections:
  params      -- paper Tables 2/3/4 (+ least-squares fit demo)
  modeled     -- paper Figure 4.3 (strategy predictions)
  validation  -- paper Figure 4.2 (model vs measured SpMV exchange)
  spmv        -- paper Figure 5.1 (SpMV strategies) + SpMM k-sweep
  overlap     -- split-phase overlap sweep (interior fraction x pods x k)
  solver      -- CG workload sweep (regime x strategy x overlap + amortized
                 model, + fused whole-solve vs host-driven loop)
  wire        -- inter-pod wire codec sweep (codec x strategy x k x pods)
  planning    -- planner setup time vs nranks (vectorized vs legacy)
  kernels     -- Pallas kernel micro-benchmarks
  roofline    -- deliverable (g): terms from the dry-run artifacts
  chaos       -- fault-injection recovery rate + verify-mode overhead
  moe_dispatch -- MoE token dispatch via the exchange stack (strategy x
                  codec x skew vs the all-to-all baseline, + plan cache)
  serving     -- multi-tenant continuous batching (arrival pattern x
                 coalescing window x strategy, p50/p99 + throughput, plus
                 a real fused-SpMM replay with parity)

``--smoke`` runs every requested section in a reduced configuration (fewer
matrices/iterations/devices).  It exists so a tier-1 test can execute the
benchmark scripts end to end and catch rot; absolute numbers from a smoke
pass are meaningless.

Every full *passing* run (all sections, no failures) also writes
``BENCH_exchange.json`` at the repo root (single-section runs and runs
with failed sections leave it untouched) -- a
machine-readable record of per-section wall times plus the wire-byte
counters of a fixed reference exchange (the numbers
``IrregularExchange.wire_bytes`` reports, per strategy x codec) and the
chaos-recovery tally (schema 2: which ladder rung cured each seeded fault
scenario, per strategy x codec) and the MoE-dispatch routing counters
(schema 3: bucketed vs uniform plan bytes per strategy, plus the
simulated plan-cache hit rate for a jittering skewed load) and the
serving record (schema 4: coalesced vs sequential p50/p99/throughput and
the >= 3x acceptance speedup on the fixed skewed burst trace, with the
deterministic simulator's trace hash) and the fused-solve record
(schema 5: host-driven CG loop vs the fused whole-solve
``lax.while_loop`` program on the 8-device reference problem at
``maxiter=120``, with the >= 2x acceptance speedup and the
one-plan-miss / one-compile cache pins) and the serving-chaos record
(schema 6: the traffic simulator draining a seeded burst trace through
the executor recovery ladder under a fault storm -- completion /
recovery / shed / deadline-miss rates, breaker probe outcomes, and the
deterministic trace hash) -- so the perf trajectory is
trackable across PRs; schema pinned by ``tests/test_benchmarks_smoke.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

#: bump when the JSON layout changes (tests pin it)
BENCH_SCHEMA = 6
BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCH_exchange.json")


def _wire_byte_counters() -> dict:
    """Wire-byte counters of a fixed reference exchange, per strategy x codec.

    Plan-level and jax-free: :func:`repro.comm.wire.scaled_wire_bytes` on
    the planned (fused) program is exactly what
    ``IrregularExchange.wire_bytes`` returns for the same arguments, so
    these counters track the executor's reporting without needing
    ``nranks`` devices in this process.
    """
    import numpy as np

    from repro.comm import wire
    from repro.comm.exchange import random_pattern
    from repro.comm.strategies import STRATEGY_NAMES, planned
    from repro.comm.topology import PodTopology

    rng = np.random.default_rng(1234)
    topo = PodTopology(npods=2, ppn=4)
    pat = random_pattern(rng, topo, local_size=16, p_connect=0.5, max_elems=8)
    out: dict = {"pattern_fingerprint": pat.fingerprint(), "codecs": {}}
    for strategy in STRATEGY_NAMES:
        sp = planned(pat, strategy, message_cap_bytes=512)
        per_codec = {}
        for codec in wire.WIRE_CODECS:
            intra, inter = wire.scaled_wire_bytes(sp, codec)
            per_codec[codec] = {"intra_pod_bytes": intra, "inter_pod_bytes": inter}
        out["codecs"][strategy] = per_codec
    return out


def _chaos_counters() -> dict:
    """Chaos-recovery tally on the same fixed reference pattern (schema 2).

    Deterministic and jax-free (numpy ladder): for each strategy x lossy
    codec, which ladder rung (retry/demote/readvise) cured each seeded
    fault scenario.  A regression that breaks a recovery path shows up as
    a diff in this committed record before any test names it.
    """
    from benchmarks.bench_chaos import chaos_outcomes

    from repro.comm import wire
    from repro.comm.strategies import STRATEGY_NAMES

    lossy = tuple(c for c in wire.WIRE_CODECS if c != "none")
    return chaos_outcomes(STRATEGY_NAMES, lossy)


def _moe_dispatch_counters() -> dict:
    """MoE routing counters on a fixed skewed load (schema 3).

    Deterministic, plan-level and jax-free: a jittering skewed routing
    stream through :class:`repro.models.RoutingBucketer` (the simulated
    plan-cache hit rate the tentpole pins at >= 90%), plus the planner's
    wire bytes for the bucketed dispatch pattern next to the uniform
    full-block all-to-all it replaces, per strategy.  The byte gap is the
    traffic the quantized prefix shipping avoids sending at all.
    """
    import numpy as np

    from repro.comm import wire
    from repro.comm.exchange import block_pattern
    from repro.comm.strategies import STRATEGY_NAMES, planned
    from repro.comm.topology import PodTopology
    from repro.models import RoutingBucketer

    topo = PodTopology(npods=2, ppn=4)
    n = topo.nranks
    block = 32
    rng = np.random.default_rng(1234)
    base = np.zeros((n, n), np.int64)
    base[:, :3] = 20  # hot experts on ranks 0..2
    np.fill_diagonal(base, 0)
    buck = RoutingBucketer(topo, block=block, quantum=8)
    bundle = None
    for _ in range(24):
        jitter = rng.integers(-3, 4, size=(n, n)) * (base > 0)
        bundle, _ = buck.step(base + jitter)
    out: dict = {
        "batches": buck.steps,
        "replans": buck.replans,
        "hit_rate": round(buck.hit_rate, 4),
        "strategies": {},
    }
    uniform = block_pattern(topo, block)
    for strategy in STRATEGY_NAMES:
        per = {}
        for name, pat in (("uniform", uniform), ("bucketed", bundle.pattern_dispatch)):
            sp = planned(pat, strategy, message_cap_bytes=512)
            intra, inter = wire.scaled_wire_bytes(sp, "none")
            per[name] = {"intra_pod_bytes": intra, "inter_pod_bytes": inter}
        out["strategies"][strategy] = per
    return out


def _serving_counters() -> dict:
    """Continuous-batching acceptance record (schema 4).

    Deterministic and jax-free: the virtual-clock simulator replays the
    fixed skewed burst trace coalesced (k <= 8) and sequentially, with
    service times from the advisor's model.  ``speedup`` is the acceptance
    criterion (>= 3x); ``trace_hash`` pins that the scheduler made the
    same decisions as the committed record -- any diff here is a scheduler
    behavior change, surfaced before any test names it.
    """
    from benchmarks.bench_serving import reference_report

    rep = reference_report()
    co, sq = rep["coalesced"], rep["sequential"]
    return {
        "speedup": round(rep["speedup"], 4),
        "max_width": rep["max_width"],
        "window_s": rep["window_s"],
        "trace_hash": rep["trace_hash"],
        "coalesced": {k: round(v, 9) for k, v in co.items()},
        "sequential": {k: round(v, 9) for k, v in sq.items()},
    }


#: fused-solve acceptance measurement, run on 8 forced host devices.  The
#: reference system is mildly ill-conditioned (shift=1e-2) so the f32
#: trajectory is deterministic and host/fused agree iteration-for-iteration
#: under the maxiter=120 horizon; tol stays above the f32 residual plateau.
_FUSED_SOLVE_CODE = """
import json, time, numpy as np
from repro.comm import cache_stats, clear_caches
from repro.comm.topology import PodTopology
from repro.solve import DeviceReductions, cg, fused_cg, spd_system
from repro.sparse import DistributedSpMV, partition_csr, thermal_like

topo = PodTopology(npods=2, ppn=4)
rng = np.random.default_rng(7)
A = spd_system(thermal_like(144, rng), shift=1e-2)
part = partition_csr(A, topo)
b = rng.normal(size=(topo.nranks, part.rows_per_rank)).astype(np.float32)
red = DeviceReductions(topo)
op = DistributedSpMV(part, strategy="two_step", use_pallas=False)
tol, maxiter = 1e-5, 120

host = cg(op, b, tol=tol, maxiter=maxiter, reductions=red)  # warm jits
t0 = time.perf_counter()
host = cg(op, b, tol=tol, maxiter=maxiter, reductions=red)
t_host = time.perf_counter() - t0

clear_caches()
# fresh op: the fused solve must plan from scratch (one plan miss)
opf = DistributedSpMV(part, strategy="two_step", use_pallas=False)
fres = fused_cg(opf, b, tol=tol, maxiter=maxiter)  # plan + trace exactly once
s = cache_stats()
assert (s.plan_misses, s.fused_misses, s.fused_hits) == (1, 1, 0), s
t0 = time.perf_counter()
fres = fused_cg(opf, b, tol=tol, maxiter=maxiter)
t_fused = time.perf_counter() - t0
s = cache_stats()
assert s.fused_hits == 1, s
assert (fres.iterations, fres.status) == (host.iterations, host.status), (
    fres.iterations, fres.status, host.iterations, host.status)
assert t_host / t_fused >= 2.0, (t_host, t_fused)  # the acceptance bar

rec = {
    "problem": {"n": A.n, "nnz": A.nnz, "shift": 1e-2, "strategy": "two_step",
                "tol": tol, "maxiter": maxiter, "devices": topo.nranks},
    "host": {"iterations": host.iterations, "status": host.status,
             "total_s": round(t_host, 6),
             "us_per_iter": round(t_host / max(host.iterations, 1) * 1e6, 1)},
    "fused": {"iterations": fres.iterations, "status": fres.status,
              "total_s": round(t_fused, 6),
              "us_per_iter": round(t_fused / max(fres.iterations, 1) * 1e6, 1)},
    "speedup": round(t_host / t_fused, 2),
    "cache": {"plan_misses": s.plan_misses, "fused_misses": s.fused_misses,
              "fused_hits": s.fused_hits},
}
print("FUSED_RECORD," + json.dumps(rec))
"""


def _serving_chaos_record() -> dict:
    """Serving-chaos acceptance record (schema 6).

    Deterministic and jax-free (:func:`benchmarks.bench_chaos.
    serving_chaos`): the traffic simulator drains a seeded burst trace
    through the executor recovery ladder under a fault storm.  The
    committed record pins the completion / recovery / shed /
    deadline-miss rates, the breaker probe outcomes, and the trace hash,
    so a regression in fault handling shows up as a diff before any test
    names it.
    """
    from benchmarks.bench_chaos import serving_chaos

    return serving_chaos()


def _fused_solve_record() -> dict:
    """Fused whole-solve acceptance record (schema 5).

    Unlike the other counters this one needs devices: it times the
    host-driven CG loop against the fused ``lax.while_loop`` program
    (:func:`repro.solve.fused_cg`) on the 8-device smoke reference
    problem at ``maxiter=120``.  ``speedup`` is the acceptance criterion
    (>= 2x, asserted in the subprocess so a regression blocks the
    write); the cache counters pin the exactly-one-plan-miss /
    one-fused-compile contract.
    """
    from benchmarks.common import run_with_devices

    out = run_with_devices(_FUSED_SOLVE_CODE, devices=8)
    line = next(l for l in out.splitlines() if l.startswith("FUSED_RECORD,"))
    return json.loads(line[len("FUSED_RECORD,"):])


def maybe_write_record(report: dict, wanted, section_names, path: str = BENCH_JSON,
                       fused_record: "dict | None" = None) -> bool:
    """Write the tracked record iff this was a FULL, PASSING run.

    The record's contract (``tests/test_benchmarks_smoke.py``) is
    ``failures == []`` with every section ok, so a broken environment must
    never clobber the healthy committed trajectory file; likewise a
    single-section iteration must not replace the cross-PR record (and only
    a full run pays for the wire counters it would otherwise discard).

    ``fused_record`` is a test seam: the fused-solve measurement spawns an
    8-device subprocess, so hermetic unit tests inject a synthetic record
    instead of paying for (and depending on) the real one.
    """
    failures = report["failures"]
    not_ok = [n for n, s in report["sections"].items() if not s["ok"]]
    if failures or not_ok:
        print(f"\n### sections failed ({failures or not_ok}); {path} left untouched")
        return False
    if set(wanted) != set(section_names):
        print(f"\n### partial run ({wanted}); {path} left untouched")
        return False
    report["wire_bytes"] = _wire_byte_counters()
    report["chaos_recovery"] = _chaos_counters()
    report["moe_dispatch"] = _moe_dispatch_counters()
    report["serving"] = _serving_counters()
    report["fused_solve"] = _fused_solve_record() if fused_record is None else fused_record
    report["serving_chaos"] = _serving_chaos_record()
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"\n### wrote {path}")
    return True


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_chaos,
        bench_kernels,
        bench_model_validation,
        bench_modeled_performance,
        bench_moe_dispatch,
        bench_overlap,
        bench_params,
        bench_planning,
        bench_roofline,
        bench_serving,
        bench_solver,
        bench_spmv,
        bench_wire,
    )

    sections = {
        "params": bench_params.main,
        "modeled": bench_modeled_performance.main,
        "validation": bench_model_validation.main,
        "spmv": bench_spmv.main,
        "overlap": bench_overlap.main,
        "solver": bench_solver.main,
        "wire": bench_wire.main,
        "planning": bench_planning.main,
        "kernels": bench_kernels.main,
        "roofline": bench_roofline.main,
        "chaos": bench_chaos.main,
        "moe_dispatch": bench_moe_dispatch.main,
        "serving": bench_serving.main,
    }
    args = sys.argv[1:]
    smoke = "--smoke" in args
    wanted = [a for a in args if not a.startswith("--")] or list(sections)
    failures = []
    report = {
        "schema": BENCH_SCHEMA,
        "smoke": smoke,
        "sections": {},
    }
    for name in wanted:
        print(f"\n### section: {name}")
        t0 = time.perf_counter()
        try:
            sections[name](smoke=smoke)
            ok = True
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            ok = False
            traceback.print_exc()
            print(f"### section {name} FAILED: {e}")
        report["sections"][name] = {
            "elapsed_s": round(time.perf_counter() - t0, 3),
            "ok": ok,
        }
    report["failures"] = failures
    maybe_write_record(report, wanted, sections)
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
