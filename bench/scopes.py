#!/usr/bin/env python3
"""Device time by program scope, the host-device clock offset, and idle
gaps labelled by the program's own spans, from one profiler trace.

The program names its device work with ``jax.named_scope``
(``repro.trace.scope``: ``exchange.*``, ``spmv.*``, ``solve.*``) and its
host work with ``repro.*`` spans (``repro.trace.span``).  In a TPU trace
each operation's metadata carries its named-scope path as the ``tf_op``
stat; ``jax.profiler.ProfileData`` shows event stats only, so this module
reads the XSpace proto itself, with the generated ``xplane_pb2`` module
loaded from its file (nothing else of the package that ships it is
imported).  Where that module is missing, :func:`read_scoped` returns
``None``.

:func:`summarize_scoped` reduces a trace over the same window as
:func:`bench.trace.reduce_trace`, and leaves that reduction as it is:

* **scope**: every leaf operation's innermost program scope; per device,
  the union of the intervals of the operations under each scope, and the
  share of busy time under any scope (``scoped_pct``);
* **clock**: per device, the smallest shift of the device's clock that puts
  no ``XLA Modules`` run before the host's ``tpu::System::Execute`` that
  launched it (runs paired by ``run_id``).  The shift applies to the
  quantities here only;
* **gaps**: each idle gap, after the shift, labelled by the innermost host
  span of either prefix, ``bench.`` or ``repro.``, at its middle.

The per-layer quantities that read scopes (:func:`exchange_device_us`,
:func:`gather_us`) are functions of a summary and the window's counters.
Run as a script, this module runs one cell traced, as ``bench/run.py
--trace 1`` does, and logs the scoped reduction of the same trace beside
the result line:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

``--keep`` copies the ``.xplane.pb`` there.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace  # noqa: E402

#: the host spans that label idle gaps: the benchmark's and the program's
SPAN_PREFIXES = ("bench.", "repro.")
#: a program scope is a name-stack component ``<layer>`` or ``<layer>.<part>``
SCOPE = re.compile(r"^(exchange|spmv|solve)(\.[A-Za-z0-9_]+)?$")
MODULES_LINE = "XLA Modules"
LAUNCH = "tpu::System::Execute"
ENQUEUE = "DoEnqueueProgram"

_XPLANE_PB2 = None


def xplane_pb2():
    """The generated XSpace proto module, loaded from its file, or ``None``."""
    global _XPLANE_PB2
    if _XPLANE_PB2 is None:
        spec = importlib.util.find_spec("tensorflow")  # found, not imported
        if spec is None or spec.origin is None:
            return None
        path = Path(spec.origin).parent / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py"
        if not path.exists():
            return None
        mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _XPLANE_PB2 = mod
    return _XPLANE_PB2


def path_scopes(tf_op: str) -> list:
    """The program scopes on a ``tf_op`` path, outermost first:
    ``"jit(f)/shard_map/spmv/spmv.diag/spmv.gather/gather:"`` ->
    ``["spmv", "spmv.diag", "spmv.gather"]``."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return [part for part in path.split("/") if SCOPE.match(part)]


def innermost_scope(tf_op: str) -> Optional[str]:
    """The last program scope on a ``tf_op`` path; ``None`` where the path
    holds none."""
    found = path_scopes(tf_op)
    return found[-1] if found else None


@dataclasses.dataclass
class ScopedTrace:
    """What one ``.xplane.pb`` holds for the scoped reduction; seconds on
    the trace's clock."""

    ops: dict  # device -> [(op, start, end, innermost scope or None)]
    modules: dict  # device -> [(run_id, start, end)]
    launches: dict  # (run_id, device ordinal or None) -> host launch start
    spans: list  # [(name, start, end)] host spans of SPAN_PREFIXES
    scopes_seen: set = dataclasses.field(default_factory=set)  # on any op's path


def _stats(plane, stats) -> dict:
    out = {}
    for s in stats:
        name = plane.stat_metadata[s.metadata_id].name
        kind = s.WhichOneof("value")
        if kind == "ref_value":
            out[name] = plane.stat_metadata[s.ref_value].name
        elif kind is not None:
            out[name] = getattr(s, kind)
    return out


def read_scoped(path: str) -> Optional[ScopedTrace]:
    """Read ``path``; ``None`` where the proto module is missing."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, launches, spans, seen = {}, {}, {}, [], set()
    for plane in space.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            paths = {mid: path_scopes(str(_stats(plane, md.stats).get("tf_op", "")))
                     for mid, md in plane.event_metadata.items()}
            scope_of = {mid: p[-1] if p else None for mid, p in paths.items()}
            dev_ops, dev_modules = [], []
            for line in plane.lines:
                if line.name not in (trace.OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    start = (line.timestamp_ns * 1000 + ev.offset_ps) * 1e-12
                    end = start + ev.duration_ps * 1e-12
                    if line.name == trace.OPS_LINE:
                        name = trace.op_name(plane.event_metadata[ev.metadata_id].name)
                        dev_ops.append((name, start, end, scope_of.get(ev.metadata_id)))
                        seen.update(paths.get(ev.metadata_id, ()))
                    else:
                        run_id = _stats(plane, ev.stats).get("run_id")
                        dev_modules.append((run_id, start, end))
            ops[plane.name], modules[plane.name] = dev_ops, dev_modules
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                launch = (float("-inf"), float("-inf"))  # the thread's last Execute
                for ev in sorted(line.events, key=lambda ev: ev.offset_ps):
                    name = plane.event_metadata[ev.metadata_id].name
                    start = (line.timestamp_ns * 1000 + ev.offset_ps) * 1e-12
                    end = start + ev.duration_ps * 1e-12
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, start, end))
                    elif name == LAUNCH:
                        launch = (start, end)
                    elif name == ENQUEUE:
                        st = _stats(plane, ev.stats)
                        if "run_id" not in st:
                            continue
                        # the launch is the Execute this enqueue runs in
                        t = launch[0] if launch[0] <= start <= launch[1] else start
                        key = (st["run_id"], st.get("device_ordinal"))
                        launches[key] = min(t, launches.get(key, t))
    return ScopedTrace(ops=ops, modules=modules, launches=launches, spans=spans,
                       scopes_seen=seen)


def device_ordinal(device: str) -> Optional[int]:
    """``"/device:TPU:3"`` -> 3."""
    tail = device.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def clock_offset(scoped: ScopedTrace, device: str) -> Optional[float]:
    """Seconds to add to ``device``'s times so that no module run starts
    before its host launch; ``None`` where no run pairs with a launch."""
    ordinal = device_ordinal(device)
    by_run = defaultdict(list)
    for (run_id, dev_ord), t in scoped.launches.items():
        by_run[run_id].append((dev_ord, t))
    needs = []
    for run_id, start, _ in scoped.modules.get(device, []):
        cands = by_run.get(run_id)
        if not cands:
            continue
        own = [t for o, t in cands if o == ordinal]
        needs.append((min(own) if own else min(t for _, t in cands)) - start)
    return max(needs) if needs else None


@dataclasses.dataclass(frozen=True)
class ScopeSummary:
    """A traced window reduced by program scope; seconds, per device plane."""

    window_s: float
    offset_s: dict  # device -> clock offset (None: no run paired)
    busy_s: dict  # device -> union of operation intervals, after the shift
    scopes: dict  # device -> {scope: merged leaf-op intervals}
    gap_s: dict  # bench./repro. span label -> idle seconds, summed over devices
    op_s: dict  # (leaf op, its scope or None) -> seconds, summed over devices

    def time_under(self, device: str, match: Callable[[str], bool]) -> float:
        """Union of the leaf operations under any scope that ``match``es."""
        ivs = [iv for s, ivs in self.scopes[device].items() if match(s) for iv in ivs]
        return sum(e - s for s, e in trace.merge(ivs))

    def scope_s(self, device: str) -> dict:
        return {s: sum(e - b for b, e in ivs) for s, ivs in self.scopes[device].items()}

    def scoped_share(self, device: str) -> float:
        busy = self.busy_s[device]
        return self.time_under(device, lambda s: True) / busy if busy else 0.0

    def has_scope(self, match: Callable[[str], bool]) -> bool:
        return any(match(s) for d in self.scopes.values() for s in d)

    def log_fields(self) -> dict:
        """The ``[scopes]`` log line's fields."""
        out = {}
        for d in self.busy_s:
            off = self.offset_s[d]
            out[f"clock_offset_us[{d}]"] = None if off is None else 1e6 * off
            out[f"scoped_pct[{d}]"] = 100.0 * self.scoped_share(d)
        return out


def summarize_scoped(scoped: ScopedTrace, window: tuple, devices: list) -> ScopeSummary:
    """Reduce ``scoped`` for ``devices`` over ``window`` (host clock)."""
    w0, w1 = window
    if w1 <= w0:
        raise ValueError(f"empty traced window {window}")
    index = trace.SpanIndex(scoped.spans)
    offsets, busy, scopes = {}, {}, {}
    gap_s, op_s = defaultdict(float), defaultdict(float)
    for dev in devices:
        off = clock_offset(scoped, dev)
        offsets[dev] = off
        shift = off or 0.0
        events = sorted(((n, s + shift, e + shift, sc) for n, s, e, sc in scoped.ops[dev]),
                        key=lambda ev: (ev[1], -ev[2]))
        clipped, by_scope = [], defaultdict(list)
        for i, (name, s, e, sc) in enumerate(events):
            # as in bench.trace.summarize: an operation that holds others is
            # busy time, but its time belongs to what it holds
            holds = i + 1 < len(events) and events[i + 1][1] < e
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if holds:
                continue
            op_s[(name, sc)] += e - s
            if sc is not None:
                by_scope[sc].append((s, e))
        merged = trace.merge(clipped)
        busy[dev] = sum(e - s for s, e in merged)
        scopes[dev] = {sc: trace.merge(ivs) for sc, ivs in by_scope.items()}
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gap_s[index.at(0.5 * (g0 + g1))] += g1 - g0
    return ScopeSummary(window_s=w1 - w0, offset_s=offsets, busy_s=busy, scopes=scopes,
                        gap_s=dict(gap_s), op_s=dict(op_s))


def window_of(spans: list, window_spans: tuple) -> Optional[tuple]:
    """The window :func:`bench.trace.reduce_trace` takes: from the first
    start to the last end of the spans named in ``window_spans``."""
    loop = [s for s in spans if s[0] in window_spans]
    if not loop:
        return None
    return min(s[1] for s in loop), max(s[2] for s in loop)


def reduce_scoped(path: str, window_spans: tuple, devices: list) -> Optional[ScopeSummary]:
    """:func:`read_scoped` then :func:`summarize_scoped`; ``None`` where the
    proto module is missing, the window spans are not there, or a device
    has no operation."""
    scoped = read_scoped(path)
    if scoped is None or not all(scoped.ops.get(d) for d in devices):
        return None
    window = window_of(scoped.spans, window_spans)
    return None if window is None else summarize_scoped(scoped, window, devices)


# ---------------------------------------------------------------------------
# The per-layer quantities that read scopes
# ---------------------------------------------------------------------------


def is_exchange(scope: str) -> bool:
    return scope == "exchange" or scope.startswith("exchange.")


def per_call_us(summary: Optional[ScopeSummary], match, calls) -> Optional[float]:
    """Per chip, the union of device time under the matching scopes in the
    window over ``calls``; the mean over chips, in microseconds.  ``None``
    without a summary, calls, or any such scope."""
    if summary is None or not calls or not summary.has_scope(match):
        return None
    per_chip = [summary.time_under(d, match) for d in summary.busy_s]
    return 1e6 * sum(per_chip) / len(per_chip) / calls


def exchange_device_us(summary, counters: dict) -> Optional[float]:
    """The exchange measured inside the loop: device time under ``exchange``
    or ``exchange.*`` a product."""
    return per_call_us(summary, is_exchange, counters.get("products"))


def gather_us(summary, counters: dict) -> Optional[float]:
    """Device time under ``spmv.gather`` (both blocks) a product, or a CG
    iteration where the window counts iterations."""
    its = counters.get("iterations")
    calls = sum(its) if its else counters.get("products")
    return per_call_us(summary, lambda s: s == "spmv.gather", calls)


# ---------------------------------------------------------------------------
# A traced run with the scoped reduction beside it
# ---------------------------------------------------------------------------


def run_scoped(root: Path, name: str, seed: int, seconds: float,
               keep: Optional[str] = None, device_kind: Optional[str] = None) -> dict:
    """Run cell ``name`` traced through :func:`bench.run.run_cell` and read
    the same trace by scope too; return its result with ``scopes`` (device
    time by scope, the costliest operations with their scopes, the unscoped
    ones, idle gaps by span, all averaged over the devices) and
    ``scoped_metrics`` added.  The ``repro.partition.*``
    and ``repro.build.*`` durations that a ``jax.monitoring`` listener
    receives go on a ``[spans]`` log line."""
    import jax.monitoring

    from bench import run

    seen: dict = {}
    host: dict = defaultdict(float)
    reduce_trace, load_loop = trace.reduce_trace, run.load_loop

    def scoped_reduce(path, window_spans, devices):
        t0 = time.perf_counter()
        summary = reduce_trace(path, window_spans, devices)
        t1 = time.perf_counter()
        seen["scopes"] = reduce_scoped(path, window_spans, devices)
        run.log("trace", bench_read_s=t1 - t0, scoped_read_s=time.perf_counter() - t1,
                scoped=seen["scopes"] is not None)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{name}-{seed}.xplane.pb"))
        return summary

    def recorded_loop(root, loop):
        cls = load_loop(root, loop)

        class Recorded(cls):
            def run(self, seconds):
                seen["window"] = super().run(seconds)
                return seen["window"]

        return Recorded

    def on_duration(event, secs, **_):
        if event.startswith("/repro/"):
            host[event[len("/repro/"):]] += secs

    trace.reduce_trace, run.load_loop = scoped_reduce, recorded_loop
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        result, lines = run.run_cell(root, name, seed, seconds, True, device_kind)
    finally:
        trace.reduce_trace, run.load_loop = reduce_trace, load_loop
        jax.monitoring.unregister_event_duration_listener(on_duration)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    run.log("spans", **{k: host[k] for k in sorted(host)
                        if k.startswith(("partition", "build"))})
    summary, window = seen.get("scopes"), seen["window"]
    counters = window.counters
    calls = counters.get("products") or counters.get("solves")
    if summary is not None:
        run.log("scopes", **summary.log_fields())
        n = len(summary.busy_s)
        scope_s = defaultdict(float)
        for d in summary.busy_s:
            for sc, v in summary.scope_s(d).items():
                scope_s[sc] += v / n
        ranked = sorted(summary.op_s.items(), key=lambda kv: -kv[1])
        result["scopes"] = {
            "scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
            "op_s": [[op, sc, v / n] for (op, sc), v in ranked[:12]],
            "unscoped_op_s": [[op, v / n] for (op, sc), v in ranked if sc is None][:10],
            "idle_gaps": {k: v / n for k, v in sorted(
                summary.gap_s.items(), key=lambda kv: -kv[1])},
            "window_s": summary.window_s,
        }
    result["scoped_metrics"] = {
        "exchange_device_us": exchange_device_us(summary, counters),
        "gather_us": gather_us(summary, counters),
        "per_call_host_us": 1e6 * window.elapsed_s / calls if calls else None,
    }
    return result


def main(argv=None) -> None:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="directory to copy the .xplane.pb into")
    args = ap.parse_args(argv)

    cell = run.load_cell(ROOT, args.workload)
    run.require_chips(cell.chips)
    run.use_compile_cache()
    result = run_scoped(ROOT, args.workload, args.seed, args.seconds, args.keep)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
