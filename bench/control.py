#!/usr/bin/env python3
"""The control: the plain reference in the program's place, in bfloat16.

The configurations state float32, so the control computes one step lower,
in bfloat16, the step that would tempt a later change.  It takes the
program's place through ``run_cell``'s ``substitute`` hook and goes
through the cell's own loop and check, which must then read it as not
correct: its numbers are the upper readings that the limits in
``bench/limits/`` are set below.

* A product: ``A @ v`` over the cell's CSR matrix with the values and ``v``
  rounded to bfloat16, each product accumulated in bfloat16.
* A solve: textbook CG with the matrix, every vector and every scalar in
  bfloat16, stopping at ``tol`` on its own residual, at ``maxiter``, or
  after 20 iterations without a new best residual.

The benchmark's own runs never run the control.  On the chip, at the cell's
own size:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402
from bench.csr import Csr  # noqa: E402

STALL = 20


def _csr_product(A: Csr, dtype):
    """A jitted ``x -> A @ x`` computed in ``dtype`` on the default device."""
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(A.row_ids().astype("int32"))
    cols = jnp.asarray(A.indices)
    vals = jnp.asarray(A.data).astype(dtype)

    @jax.jit
    def product(x):
        terms = vals * x.astype(dtype)[cols]
        return jax.ops.segment_sum(terms, rows, num_segments=A.n, indices_are_sorted=True)

    return product


def _cg(product, dtype):
    """A jitted plain CG, every vector and scalar in ``dtype``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def cg(b, tol, maxiter):
        b = b.astype(dtype)
        bnorm = jnp.sqrt(jnp.vdot(b, b))
        x = jnp.zeros_like(b)

        def cond(c):
            _, _, _, rs, it, best, best_it = c
            rel = jnp.sqrt(rs) / bnorm
            return (rel > tol) & (it < maxiter) & (it - best_it < STALL)

        def body(c):
            x, r, p, rs, it, best, best_it = c
            Ap = product(p)
            alpha = rs / jnp.vdot(p, Ap)
            x = x + alpha * p
            r = r - alpha * Ap
            rs_new = jnp.vdot(r, r)
            p = r + (rs_new / rs) * p
            improved = rs_new < best
            return (x, r, p, rs_new, it + 1, jnp.where(improved, rs_new, best),
                    jnp.where(improved, it + 1, best_it))

        rs = jnp.vdot(b, b)
        c = (x, b, b, rs, 0, rs, 0)
        x, _, _, rs, it, _, _ = jax.lax.while_loop(cond, body, c)
        return x, jnp.sqrt(rs) / bnorm <= tol, it

    return cg


def substitute(dtype: str = "bfloat16"):
    """``run_cell``'s ``substitute``: the reference in ``dtype`` in place of
    ``DistributedSpMV.__call__`` and of ``repro.solve.fused_cg``."""
    import jax.numpy as jnp
    import numpy as np

    import repro.solve
    from repro.solve.krylov import SolveResult
    from repro.sparse.spmv import DistributedSpMV

    dt = jnp.dtype(dtype)

    @contextlib.contextmanager
    def stand_in(A: Csr):
        product = _csr_product(A, dt)
        cg = _cg(product, dt)

        def call(self, v):
            x = jnp.asarray(np.asarray(v).reshape(-1))
            return product(x).astype(v.dtype).reshape(v.shape)

        def fused_cg(op, b, tol=1e-6, maxiter=500, **_):
            x, ok, it = cg(jnp.asarray(np.asarray(b).reshape(-1)), tol, maxiter)
            x = np.asarray(x.astype(jnp.float32)).reshape(np.shape(b))
            return SolveResult(x=x, converged=bool(ok), iterations=int(it), residuals=(),
                               matvecs=int(it), status="converged" if ok else "maxiter")

        with mock.patch.object(DistributedSpMV, "__call__", call), \
                mock.patch.object(repro.solve, "fused_cg", fused_cg):
            yield

    return stand_in


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = run.load_cell(ROOT, args.workload)
    run.require_chips(cell.chips)
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines = run.run_cell(ROOT, args.workload, seed, args.seconds, False,
                                     substitute=substitute())
        for line in lines:
            print(line, file=sys.stderr, flush=True)
        print(json.dumps({"control": "bfloat16", "seed": seed, "correct": result["correct"],
                          "failed": result["failed"], "attempted": result["attempted"],
                          "check": result["check"]}), flush=True)


if __name__ == "__main__":
    main()
