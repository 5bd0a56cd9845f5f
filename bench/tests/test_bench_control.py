"""The check fails the control and every fault a cell can have.

Each stand-in takes the program's place through ``run_cell``'s
``substitute`` hook and the rest of a run goes on as on the chip, at a size
the CPU holds: the bfloat16 control (:mod:`bench.control`), a step that
returns its state unchanged, half of the answer left out, the exchange
between chips left out (only where a cell has chips to exchange between),
and one value of an answer altered where it is produced.
"""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from unittest import mock

import pytest

from bench import run
from bench.tests import tinyroot

ROOT = tinyroot.ROOT


def broken(fault: str):
    """A ``substitute`` that puts the real program, broken by ``fault``, in
    the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.solve
    from repro.comm.strategies import IrregularExchange
    from repro.sparse.spmv import DistributedSpMV

    solve = repro.solve.fused_cg
    product = DistributedSpMV.__call__
    exchange = IrregularExchange.__call__

    def fused_cg(op, b, **kw):
        res = solve(op, b, **kw)
        x = np.array(res.x)
        if fault == "unchanged":
            x = np.zeros_like(x)  # the start, x0 = 0
        elif fault == "half":
            x.reshape(-1)[: x.size // 2] = 0.0
        elif fault == "altered":
            x.reshape(-1)[x.size // 3] += 1.0
        return dataclasses.replace(res, x=x)

    def call(self, v):
        w = product(self, v)
        if fault == "unchanged":
            return v
        if fault in ("half", "altered"):
            h = np.array(w)
            if fault == "half":
                h[:, : h.shape[1] // 2] = 0.0
            else:
                h[0, 7] += 1.0
            return jax.device_put(h, w.sharding)
        return w

    def no_exchange(self, v):
        return jnp.zeros_like(exchange(self, v))

    @contextlib.contextmanager
    def stand_in(A):
        with contextlib.ExitStack() as stack:
            stack.enter_context(mock.patch.object(repro.solve, "fused_cg", fused_cg))
            stack.enter_context(mock.patch.object(DistributedSpMV, "__call__", call))
            if fault == "exchange":
                stack.enter_context(mock.patch.object(IrregularExchange, "__call__",
                                                      no_exchange))
            yield

    return stand_in


def stand_in(name: str):
    if name == "control":
        from bench import control

        return control.substitute()
    return broken(name)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("tiny"))


def test_sound_program_passes(tiny):
    result, lines = run.run_cell(tiny, "stencil2d-cg", 21, 0.3, False,
                                 device_kind="TPU v5 lite", substitute=broken("none"))
    assert result["correct"], lines


@pytest.mark.parametrize("name", ["control", "unchanged", "half", "altered"])
def test_solve_cell_fails(tiny, name):
    result, lines = run.run_cell(tiny, "stencil2d-cg", 22, 0.3, False,
                                 device_kind="TPU v5 lite", substitute=stand_in(name))
    assert not result["correct"], lines
    assert result["failed"] > 0
    assert lines[-2].startswith("[check] true_residual_max=")


PRODUCT_CASES = ["none", "control", "unchanged", "half", "exchange", "altered"]


@pytest.fixture(scope="module")
def product_results(tiny):
    """Every stand-in on the four-rank product cell, in one child process
    with four forced CPU devices."""
    code = textwrap.dedent(f"""
        import json, sys
        from pathlib import Path
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        from bench import run
        from bench.tests.test_bench_control import stand_in
        out = {{}}
        for name in {PRODUCT_CASES!r}:
            res, lines = run.run_cell(Path({str(tiny)!r}), "er-spmv-4chip", 23, 0.3, False,
                                      device_kind="TPU v5 lite", substitute=stand_in(name))
            out[name] = {{"result": res, "lines": lines}}
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", PRODUCT_CASES)
def test_product_cell(product_results, name):
    got = product_results[name]
    assert got["result"]["correct"] == (name == "none"), got["lines"]
    assert got["lines"][-1].startswith("[check] product_error_max=")
