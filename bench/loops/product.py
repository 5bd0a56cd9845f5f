"""Back-to-back distributed SpMVs, each ending in ``block_until_ready``.

Mix parameters: ``strategy``, ``payload_width``, ``input_pool`` (vectors
drawn from the seed and placed on the mesh before the window, cycled over),
``check_share`` (the share of calls, drawn from the seed, whose answer is
kept for the check; the first always is) and ``exchange_probe_calls`` (the
exchanges timed alone after the window of a traced run).
"""

from __future__ import annotations

import numpy as np

from bench import check
from bench.csr import Csr, spmv_f64
from bench.loops import Window, closed_loop


class Loop:
    window_spans = ("bench.product", "bench.sync")

    def __init__(self, traffic: dict, part, A: Csr, dtype, seed: int, spans):
        from repro.comm.topology import shard_ranks
        from repro.sparse import spmv

        self.traffic, self.A, self.spans = traffic, A, spans
        self.op = spmv.DistributedSpMV(part, strategy=traffic["strategy"],
                                       payload_width=int(traffic["payload_width"]))
        g, L = part.topo.nranks, part.rows_per_rank
        rng = np.random.default_rng([seed, 1])
        self.inputs = [
            rng.standard_normal(A.n).astype(dtype)
            for _ in range(int(traffic["input_pool"]))
        ]
        self.placed = [shard_ranks(v.reshape(g, L), self.op.mesh) for v in self.inputs]
        self.keep = np.random.default_rng([seed, 2])
        self.kept: list = []

    def warm(self) -> None:
        for v in self.placed[:2]:
            self.op(v).block_until_ready()
        self.op.halo(self.placed[0]).block_until_ready()

    def run(self, seconds: float) -> Window:
        share = float(self.traffic["check_share"])
        n = len(self.placed)

        def call(i):
            with self.spans("bench.product"):
                w = self.op(self.placed[i % n])
            with self.spans("bench.sync"):
                w.block_until_ready()
            if i == 0 or self.keep.random() < share:
                self.kept.append((i % n, w))

        w = closed_loop(seconds, call)
        w.counters["products"] = w.calls
        return w

    def probe(self) -> None:
        """Time the exchange alone: ``halo(v)`` to ``block_until_ready``."""
        for i in range(int(self.traffic["exchange_probe_calls"])):
            with self.spans("bench.exchange"):
                self.op.halo(self.placed[i % len(self.placed)]).block_until_ready()

    def free(self) -> None:
        self.kept = [(j, np.asarray(w)) for j, w in self.kept]
        del self.op, self.placed

    def check(self, limits: dict) -> tuple[dict, int]:
        """``({name: value}, failed)`` over the kept answers: the worst
        product error, and the answers over the limit."""
        refs = {}
        errs = []
        for j, w in self.kept:
            if j not in refs:
                refs[j] = spmv_f64(self.A, self.inputs[j])
            errs.append(check.product_error(refs[j], w))
        failed = sum(not (e <= limits["product_error_max"]) for e in errs)
        return {"product_error_max": max(errs)}, failed
