"""Back-to-back whole solves through the program's whole-solve entry.

Mix parameters: ``solver`` (an attribute of ``repro.solve``, e.g.
``fused_cg``), ``strategy``, ``tol``, ``maxiter`` and ``rhs_pool``: one
solver, each solve taking its right-hand side from a pool of ``rhs_pool``
vectors drawn from the seed before the window.  Every solve's answer is
checked by its true residual.
"""

from __future__ import annotations

import numpy as np

from bench import check
from bench.csr import Csr
from bench.loops import Window, closed_loop


class Loop:
    window_spans = ("bench.rhs", "bench.solve")

    def __init__(self, traffic: dict, part, A: Csr, dtype, seed: int, spans):
        import repro.solve
        from repro.sparse import spmv

        self.traffic, self.A, self.spans = traffic, A, spans
        self.op = spmv.DistributedSpMV(part, strategy=traffic["strategy"])
        g, L = part.topo.nranks, part.rows_per_rank
        rng = np.random.default_rng([seed, 1])
        self.pool = [
            rng.standard_normal(A.n).astype(dtype).reshape(g, L)
            for _ in range(int(traffic["rhs_pool"]))
        ]
        self.solver = getattr(repro.solve, traffic["solver"])
        self.answers: list = []

    def solve(self, j: int):
        return self.solver(self.op, self.pool[j], tol=float(self.traffic["tol"]),
                           maxiter=int(self.traffic["maxiter"]))

    def warm(self) -> None:
        self.solve(0)

    def run(self, seconds: float) -> Window:
        def call(i):
            with self.spans("bench.rhs"):
                j = i % len(self.pool)
            with self.spans("bench.solve"):
                res = self.solve(j)
            self.answers.append((j, res.x, res.converged, res.iterations))

        w = closed_loop(seconds, call)
        w.counters["solves"] = w.calls
        w.counters["iterations"] = [a[3] for a in self.answers]
        return w

    def probe(self) -> None:
        """No extra calls in a traced run."""

    def free(self) -> None:
        del self.op

    def check(self, limits: dict) -> tuple[dict, int]:
        """``({name: value}, failed)``: the worst true residual over every
        solve, and the solves that did not converge or answered wrong."""
        res = [check.true_residual(self.A, self.pool[j], x) for j, x, _, _ in self.answers]
        bad = [not (r <= limits["true_residual_max"]) or not a[2]
               for r, a in zip(res, self.answers)]
        unconverged = sum(1 for a in self.answers if not a[2])
        return {"true_residual_max": max(res), "unconverged": unconverged}, sum(bad)
