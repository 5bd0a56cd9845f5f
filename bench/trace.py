"""Host spans, and the reduction of a profiler trace to device busy time.

The benchmark marks its own calls into each layer with :class:`Spans`
(``bench.rhs``, ``bench.product``, ``bench.exchange``, ``bench.solve``,
``bench.sync``, ``bench.partition``).  Every span is kept in memory with the
host clock, and in a traced run it is also a ``TraceAnnotation`` in the
profiler's trace, on the same clock as the device's operations.

:func:`reduce_trace` reads an ``.xplane.pb``: per device, the union of the
operation intervals inside the traced window (busy time), the time of each
operation by its HLO name (an operation that holds others, such as a while
loop, leaves its time to them), and every idle gap labelled by the
innermost ``bench.*`` span the host was in at the gap's middle.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import itertools
import os
import time
from collections import defaultdict
from typing import Iterable, Optional

SPAN_PREFIX = "bench."
NO_SPAN = "host outside bench spans"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Spans:
    """Records ``(name, start_s, end_s)`` by the host clock; in a traced run
    each span is also a ``jax.profiler.TraceAnnotation``."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    """A traced window, reduced.  Times are seconds; per-device values are
    keyed by the device plane's name."""

    window_s: float
    busy_s: dict  # device -> union of operation intervals in the window
    op_s: dict  # HLO op name -> seconds, summed over devices
    gap_s: dict  # host span label -> idle seconds, summed over devices

    @property
    def ndevices(self) -> int:
        return len(self.busy_s)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def idle_share(self, device: str) -> float:
        return 1.0 - self.busy_s[device] / self.window_s

    def mean_idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The most expensive operations and the idle gaps by host span,
        each averaged over the devices, largest first."""
        n = self.ndevices

        def ranked(d):
            return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(self.op_s), "idle_gaps": ranked(self.gap_s)}


def merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class SpanIndex:
    """Finds the innermost ``bench.*`` span that contains a time."""

    def __init__(self, spans: list[tuple[str, float, float]]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.reach = list(itertools.accumulate((s[2] for s in self.spans), max))

    def at(self, t: float) -> str:
        """The latest-starting span that contains ``t``, or :data:`NO_SPAN`."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] > t:
            if self.spans[i][2] > t:
                return self.spans[i][0]
            i -= 1
        return NO_SPAN


def summarize(
    ops: dict,
    spans: list[tuple[str, float, float]],
    window: tuple[float, float],
) -> TraceSummary:
    """Reduce ``ops`` (device -> list of ``(name, start, end)``) over
    ``window``; ``spans`` label the idle gaps.  All times in seconds."""
    w0, w1 = window
    if w1 <= w0:
        raise ValueError(f"empty traced window {window}")
    busy, op_s, gap_s = {}, defaultdict(float), defaultdict(float)
    index = SpanIndex(spans)
    for dev, events in ops.items():
        events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        clipped = []
        for i, (name, s, e) in enumerate(events):
            # an operation that holds others (a while loop holds its body's
            # operations) counts toward busy time, but its time is theirs
            holds = i + 1 < len(events) and events[i + 1][1] < e
            s, e = max(s, w0), min(e, w1)
            if e > s:
                clipped.append((s, e))
                if not holds:
                    op_s[name] += e - s
        merged = merge(clipped)
        busy[dev] = sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gap_s[index.at(0.5 * (g0 + g1))] += g1 - g0
    return TraceSummary(window_s=w1 - w0, busy_s=busy, op_s=dict(op_s), gap_s=dict(gap_s))


def op_name(event_name: str) -> str:
    """The HLO name of a device event named by its whole instruction,
    ``"%fusion.10 = f32[...] fusion(...)"`` -> ``"fusion.10"``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xspace(path: str):
    """Device operations and host ``bench.*`` spans from one ``.xplane.pb``:
    ``({device: [(op, start_s, end_s)]}, [(span, start_s, end_s)])``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            ops[plane.name] = [
                (op_name(ev.name), ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ln in lines for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in ln.events if ev.name.startswith(SPAN_PREFIX)
                )
    return ops, spans


def find_xspace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def reduce_trace(path: str, window_spans: tuple[str, ...],
                 devices: list[str]) -> Optional[TraceSummary]:
    """The trace at ``path`` reduced, for the device planes named in
    ``devices``, over the window that the spans named in ``window_spans``
    cover, from the first one's start to the last one's end.  ``None`` where
    the trace holds no such span, or no operation of one of those devices.""" 
    found, spans = read_xspace(path)
    loop = [s for s in spans if s[0] in window_spans]
    if not loop or not all(found.get(d) for d in devices):
        return None
    ops = {d: found[d] for d in devices}
    window = (min(s[1] for s in loop), max(s[2] for s in loop))
    return summarize(ops, spans, window)
