#!/usr/bin/env python3
"""Run one benchmark cell on the chip; print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything comes from files, found by the names in ``BENCHMARK.json``:

* the cell's configuration, ``configs[].file`` (``bench/configs/<config>.json``):
  the matrix generator (``bench/matrices/<generator>.py``) and its sizes,
  the precision, and the topology for 1 and for 4 chips;
* its traffic mix, ``bench/traffic/<traffic>.json``: data that names its
  loop kind, ``bench/loops/<loop>.py`` (:mod:`bench.loops`), and that
  loop's parameters;
* its limits for the check, ``bench/limits/<workload>.json``;
* each metric, ``bench/metrics/<metric>.py``, whose ``read(run)`` reduces a
  :class:`RunRecord` to one number, or to ``None`` where it finds nothing.

One run, in one process that starts no other: find the chips (no TPU, or
fewer chips than the cell asks for, exits non-zero before any result);
generate the matrix and inputs from ``--seed`` (logged on a ``[data]``
line; set-up starts once the data exists on the host); partition and build
the operator through the program's entry points; warm up (set-up ends here);
measure a closed loop for ``--seconds`` (with ``--trace 1`` under the
profiler, and the per-layer metrics in place of the end-to-end ones); read
the peak memory; check the answers against the float64 reference.  The
check's numbers and limits are the last lines on stderr, and the last key
of the result line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):  # the benchmark, and the program under test
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import loops, roofline, trace  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

#: fixed in-checkout directory for JAX's persistent compilation cache (the
#: path is part of the cache key, so it must not move between runs); the
#: program's entry points default to the same directory
CACHE_DIR = ROOT / ".jax_cache"


def log(kind: str, **fields) -> None:
    print(f"[{kind}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Files, found by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # this cell's entries of BENCHMARK.json's end_to_end
    per_layer: list  # and of its per_layer


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json") from None
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(root / entry["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_generator(root: Path, name: str):
    return load_module(root / "bench" / "matrices" / f"{name}.py").generate


def load_loop(root: Path, name: str):
    return load_module(root / "bench" / "loops" / f"{name}.py").Loop


def load_reader(root: Path, metric: str):
    return load_module(root / "bench" / "metrics" / f"{metric}.py").read


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunRecord:
    """Everything a metric's reader may read."""

    setup_s: float
    window: loops.Window
    spans: trace.Spans
    work: list  # roofline.RankWork per rank
    rank_devices: list  # trace plane name of each rank's device
    peaks: object
    trace: Optional[trace.TraceSummary] = None


class CompileCount:
    """Counts JAX compile requests and persistent-cache misses while open."""

    def __init__(self):
        self.requests = self.misses = 0

    def _on(self, name: str, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def counting(self):
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_listener(self._on)


def require_chips(chips: int):
    """The TPU devices, or exit non-zero: the benchmark never falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPUs, JAX found {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """Keep every compiled program in the checkout's fixed cache directory.

    The benchmark's cache is always inside its checkout, so two checkouts
    measured side by side share no compiled program.  An inherited
    ``JAX_COMPILATION_CACHE_DIR`` is overridden, and the log says so.
    """
    import os

    import jax

    inherited = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    log("cache", dir=CACHE_DIR, **({"overrides": inherited} if inherited else {}))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(root: Path, name: str, seed: int, seconds: float, traced: bool,
             device_kind: Optional[str] = None, substitute=None) -> tuple[dict, list]:
    """Run one cell on the devices JAX has; return ``(result, check lines)``.

    ``device_kind`` names the peaks' row where the devices are not the
    chip's (the CPU tests); by default it is the devices' own kind.
    ``substitute(A)``, given the cell's matrix, returns a context in which a
    stand-in takes the program's place from the build to the check: the
    control (:mod:`bench.control`) and the tests' broken programs.
    """
    from repro import comm
    from repro.comm.topology import PodTopology
    from repro.sparse import matrices

    # the program's caches as a fresh process has them: its whole-solve
    # cache keeps the operands of the first matrix of a sparsity pattern
    comm.clear_caches()
    cell = load_cell(root, name)
    cfg, traffic = cell.config, cell.traffic
    spans = trace.Spans(traced=traced)
    compiles = CompileCount()

    t_data = time.perf_counter()
    A = load_generator(root, cfg["generator"])(cfg, seed)
    log("data", rows=A.n, nnz=A.nnz, seconds=time.perf_counter() - t_data)
    # set-up starts once the data exists on the host: generation is the
    # benchmark's own fixed work, which no change to the program can move
    t_setup = time.perf_counter()
    topo = PodTopology(**cfg["topology"][str(cell.chips)])
    # the program gets its own copy, so nothing it does reaches the reference
    M = matrices.CSRMatrix(n=A.n, indptr=A.indptr.copy(), indices=A.indices.copy(),
                           data=A.data.copy())
    with contextlib.ExitStack() as stand_in:
        if substitute is not None:
            stand_in.enter_context(substitute(A))
        return _measure(root, cell, A, M, topo, seed, seconds, traced, device_kind,
                        spans, compiles, t_setup)


def _measure(root, cell, A, M, topo, seed, seconds, traced, device_kind, spans,
             compiles, t_setup) -> tuple[dict, list]:
    """Build, warm, measure and check: the part of a run that the program,
    or a stand-in in its place, serves."""
    import jax
    import numpy as np

    from repro.sparse import partition

    cfg, traffic = cell.config, cell.traffic
    with compiles.counting():
        with spans("bench.partition"):
            part = partition.partition_csr(M, topo)
        mix = load_loop(root, traffic["loop"])(traffic, part, A, np.dtype(cfg["dtype"]),
                                               seed, spans)
        devices = list(mix.op.mesh.devices.flat)
        mix.warm()
    setup_s = time.perf_counter() - t_setup
    log("setup", setup_s=setup_s, partition_s=sum(spans.durations("bench.partition")),
        halo=part.halo_width, compile_requests=compiles.requests,
        cache_misses=compiles.misses, strategy=mix.op.strategy)

    kind = device_kind or devices[0].device_kind
    planes = [f"{trace.DEVICE_PLANE_PREFIX}{d.id}" for d in devices]
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
        requests0, misses0 = compiles.requests, compiles.misses
        with compiles.counting():
            if traced:
                jax.profiler.start_trace(log_dir)
            try:
                window = mix.run(seconds)
            finally:
                if traced:
                    jax.profiler.stop_trace()
        log("window", calls=window.calls, elapsed_s=window.elapsed_s,
            compile_requests=compiles.requests - requests0,
            cache_misses=compiles.misses - misses0)
        summary = None
        if traced:
            mix.probe()
            t0 = time.perf_counter()
            summary = trace.reduce_trace(trace.find_xspace(log_dir), mix.window_spans,
                                         planes)
            log("trace", read_s=time.perf_counter() - t0,
                **({"window_s": summary.window_s} | {
                    f"idle_pct[{d}]": 100.0 * summary.idle_share(d)
                    for d in summary.busy_s} if summary else {}))
    memory = memory_peak_bytes(devices)
    mix.free()

    record = RunRecord(
        setup_s=setup_s, window=window, spans=spans,
        work=roofline.rank_work(A, topo.nranks),
        rank_devices=planes,
        peaks=peaks_for(kind), trace=summary,
    )
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = load_reader(root, m["name"])(record)
        if value is None and not traced:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t0 = time.perf_counter()
    numbers, failed = mix.check(cell.limits)
    log("reference", seconds=time.perf_counter() - t0)
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in numbers.items()}
    correct = window.calls > 0 and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": window.calls, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["check"] = checks
    lines = [f"[check] {k}={c['value']} limit={c['limit']}" for k, c in checks.items()]
    return result, lines


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    require_chips(cell.chips)
    use_compile_cache()
    result, lines = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
