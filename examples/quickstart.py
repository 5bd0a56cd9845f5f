"""Quickstart: the paper's pipeline in ~80 lines.

1. Build an irregular communication pattern (a distributed SpMV halo).
2. Ask the model-driven advisor (paper §4.6) which node-aware strategy wins
   -- including the payload-width effect: batched ``k``-column payloads scale
   the byte terms while message counts stay fixed, which can flip the winner.
3. Execute every strategy and verify identical results: single-vector SpMV,
   the fused multi-vector ``matmat`` (ONE exchange for all ``k`` columns),
   and the split-phase ``overlap=True`` pipeline.

Runs on 1 CPU device (the strategies need >= nranks devices, so the
execution step self-relaunches with 8 forced host devices).

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

K = 8  # multi-vector payload width for the SpMM demo


def main() -> None:
    from repro.comm.topology import PodTopology
    from repro.core import advise
    from repro.sparse import audikw_like, partition_csr

    rng = np.random.default_rng(0)
    topo = PodTopology(npods=2, ppn=4)

    # 1. the paper's case study: a row-partitioned sparse matrix induces an
    #    irregular point-to-point pattern
    A = audikw_like(128, rng)
    part = partition_csr(A, topo)
    pattern = part.pattern.to_comm_pattern()
    print(f"matrix n={A.n} nnz={A.nnz}; irregular pattern: "
          f"{len(pattern.messages)} messages, stats={pattern.stats()}\n")

    # 2. model-driven strategy selection (Table 6 composites), and how the
    #    batched payload width k moves the ranking (PatternStats.widened)
    for k in (1, K):
        advice = advise(pattern, machine="tpu_v5e_pod", payload_width=k)
        print(f"advisor ranking (TPU registry, payload_width={k}):")
        print(advice.table())
        print(f"-> best at k={k}: {advice.best.key}\n")

    # 3. execute all strategies on 8 host devices and verify
    if os.environ.get("_QS_CHILD") != "1":
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # never compete for an accelerator
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["_QS_CHILD"] = "1"
        env["PYTHONPATH"] = os.pathsep.join(sys.path)
        print("executing strategies on 8 host devices...")
        out = subprocess.run([sys.executable, __file__], env=env,
                             capture_output=True, text=True)
        print(out.stdout[out.stdout.find("EXECUTION"):] or out.stderr[-2000:])
        return

    print("EXECUTION")
    from repro.sparse import build

    v = rng.normal(size=(A.n,)).astype(np.float32)
    V = rng.normal(size=(A.n, K)).astype(np.float32)
    want_v, want_V = A.spmv(v), A.spmm(V)
    for strat in ("standard", "two_step", "three_step", "split"):
        # single vector, barrier exchange
        sp = build(A, topo, strategy=strat, use_pallas=True, payload_width=K)
        out = np.asarray(sp(v.reshape(topo.nranks, -1))).reshape(-1)
        np.testing.assert_allclose(out, want_v, rtol=1e-4, atol=1e-4)
        # multi-vector: matmat runs ONE exchange + one fused blocked-ELL SpMM
        W = np.asarray(sp.matmat(V.reshape(topo.nranks, -1, K)))
        np.testing.assert_allclose(W.reshape(A.n, K), want_V, rtol=1e-4, atol=1e-4)
        # split-phase overlap: interior tiles compute during the inter-node
        # phase; results are bitwise-identical to the barrier path
        ov = build(A, topo, strategy=strat, use_pallas=True, overlap=True)
        np.testing.assert_array_equal(
            np.asarray(ov.matmat(V.reshape(topo.nranks, -1, K))), W
        )
        wi, we = sp.wire_bytes
        print(f"  {strat:11s} OK (spmv + matmat k={K} + overlap)   "
              f"intra-pod {wi:6d} B   inter-pod {we:6d} B")


if __name__ == "__main__":
    main()
