"""The harness finds every item by name, runs a cell end to end on the CPU at
a tiny size, and refuses to run without a TPU."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import run
from bench.tests import tinyroot

ROOT = tinyroot.ROOT


def test_benchmark_json_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(ROOT, w["name"])
        run.load_generator(ROOT, cell.config["generator"])
        assert callable(run.load_loop(ROOT, cell.traffic["loop"]))
        assert cell.limits
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.load_reader(ROOT, m["name"]))


def test_setup_s_is_reported_in_every_cell():
    """``setup_s`` names no cells, so a cell that a later entry adds reports it too."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
    for w in bench["workloads"]:
        cell = run.load_cell(ROOT, w["name"])
        assert "setup_s" in [m["name"] for m in cell.end_to_end]


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a limits file and a metric added as new
    files and new BENCHMARK.json entries are found by name."""
    root = tinyroot.make(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/stencil2d-1024.json").read_text())
    cfg.update(name="stencil2d-32", grid_side=32)
    (root / "bench/configs/stencil2d-32.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/cg_short.json").write_text(json.dumps(
        {"loop": "solve", "solver": "fused_cg", "strategy": "standard", "tol": 1e-4,
         "maxiter": 50, "rhs_pool": 2}))
    (root / "bench/limits/tiny-cg.json").write_text(json.dumps(
        {"true_residual_max": 1e-3, "unconverged": 0}))
    (root / "bench/metrics/solves.py").write_text(
        "def read(run):\n    n = run.window.counters.get('solves')\n"
        "    return float(n) if n else None\n")
    bench["configs"].append({"name": "stencil2d-32", "source": "test",
                             "file": "bench/configs/stencil2d-32.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny-cg", "config": "stencil2d-32",
                               "traffic": "cg_short", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "solves", "unit": "solves", "better": "higher",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["tiny-cg"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.load_cell(root, "tiny-cg")
    assert cell.config["grid_side"] == 32 and cell.traffic["tol"] == 1e-4
    assert {m["name"] for m in cell.end_to_end} == {"solves", "setup_s"}
    result, lines = run.run_cell(root, "tiny-cg", seed=3, seconds=0.3, traced=False,
                                 device_kind="TPU v5 lite")
    assert result["correct"], lines
    assert result["metrics"]["solves"]["value"] == result["attempted"] > 0


#: a loop kind that is not in the benchmark: k products at once through
#: ``DistributedSpMV.matmat``, each column checked against the reference
MATMAT_LOOP = textwrap.dedent("""
    import numpy as np

    from bench import check
    from bench.csr import spmv_f64
    from bench.loops import closed_loop


    class Loop:
        window_spans = ("bench.product", "bench.sync")

        def __init__(self, traffic, part, A, dtype, seed, spans):
            from repro.comm.topology import shard_ranks
            from repro.sparse import spmv

            k = int(traffic["columns"])
            self.A, self.spans = A, spans
            self.op = spmv.DistributedSpMV(part, strategy=traffic["strategy"],
                                           payload_width=k)
            g, L = part.topo.nranks, part.rows_per_rank
            self.V = np.random.default_rng([seed, 1]).standard_normal((A.n, k)).astype(dtype)
            self.placed = shard_ranks(self.V.reshape(g, L, k), self.op.mesh)

        def warm(self):
            self.op.matmat(self.placed).block_until_ready()

        def run(self, seconds):
            def call(i):
                with self.spans("bench.product"):
                    self.W = self.op.matmat(self.placed)
                with self.spans("bench.sync"):
                    self.W.block_until_ready()

            w = closed_loop(seconds, call)
            w.counters["matmats"] = w.calls
            return w

        def probe(self):
            pass

        def free(self):
            self.W = np.asarray(self.W).reshape(self.A.n, -1)
            del self.op, self.placed

        def check(self, limits):
            errs = [check.product_error(spmv_f64(self.A, self.V[:, c]), self.W[:, c])
                    for c in range(self.V.shape[1])]
            return {"product_error_max": max(errs)}, sum(
                not (e <= limits["product_error_max"]) for e in errs)
""")


def test_a_new_loop_kind_needs_only_new_files(tmp_path):
    """A loop kind that the benchmark does not have, added as a new file under
    ``bench/loops/`` with a mix, a cell and a metric that name it, runs end to
    end and is checked."""
    root = tinyroot.make(tmp_path)
    (root / "bench/loops/matmat.py").write_text(MATMAT_LOOP)
    (root / "bench/traffic/spmm4_stream.json").write_text(json.dumps(
        {"loop": "matmat", "strategy": "standard", "columns": 4}))
    (root / "bench/limits/tiny-spmm4.json").write_text(json.dumps({"product_error_max": 1e-5}))
    (root / "bench/metrics/matmat_us.py").write_text(
        "def read(run):\n    n = run.window.counters.get('matmats')\n"
        "    return 1e6 * run.window.elapsed_s / n if n else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-spmm4", "config": "stencil2d-1024",
                               "traffic": "spmm4_stream", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "matmat_us", "unit": "us", "better": "lower",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["tiny-spmm4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, lines = run.run_cell(root, "tiny-spmm4", seed=2**31 + 5, seconds=0.3,
                                 traced=False, device_kind="TPU v5 lite")
    assert result["correct"], lines
    assert set(result["metrics"]) == {"matmat_us", "setup_s"}
    assert result["check"]["product_error_max"]["value"] < 1e-6


def test_unknown_workload_exits():
    with pytest.raises(SystemExit):
        run.load_cell(ROOT, "no-such-cell")


def test_solve_cell_end_to_end_on_cpu(tmp_path):
    root = tinyroot.make(tmp_path)
    result, lines = run.run_cell(root, "stencil2d-cg", seed=2**31 + 11, seconds=0.5,
                                 traced=False, device_kind="TPU v5 lite")
    assert result["correct"] and result["failed"] == 0, lines
    assert set(result["metrics"]) == {"cg_solve_ms", "setup_s"}
    assert list(result)[-1] == "check"
    assert result["check"]["true_residual_max"]["value"] <= 1e-5
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_traced_solve_cell_reports_per_layer_on_cpu(tmp_path):
    root = tinyroot.make(tmp_path)
    result, _ = run.run_cell(root, "stencil2d-cg", seed=5, seconds=0.3, traced=True,
                             device_kind="TPU v5 lite")
    # the CPU trace has no TPU device plane: the device metrics stay silent
    assert set(result["metrics"]) == {"partition_s", "cg_iters"}
    assert result["metrics"]["cg_iters"]["value"] > 1


def test_command_without_tpu_fails_before_any_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "stencil2d-cg",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_four_rank_product_cell_on_cpu_devices(tmp_path):
    """The product loop over a 2 x 2 mesh of forced CPU devices, traced and not."""
    root = tinyroot.make(tmp_path)
    code = textwrap.dedent(f"""
        import json, sys
        from pathlib import Path
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        from bench import run
        out = {{}}
        for traced in (False, True):
            res, _ = run.run_cell(Path({str(root)!r}), "er-spmv-4chip", 2**31 + 3, 0.5,
                                  traced, device_kind="TPU v5 lite")
            out[str(traced)] = res
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    plain, traced = out["False"], out["True"]
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"matvec_us", "matvec_p95_us", "setup_s"}
    assert set(traced["metrics"]) == {"partition_s", "exchange_us"}
    assert plain["device"]["count"] == 4
    assert plain["check"]["product_error_max"]["value"] < 1e-5
