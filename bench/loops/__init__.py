"""The traffic generator: a closed loop over the program, driven by a mix file.

A traffic mix (``bench/traffic/<mix>.json``) is data: it names its ``loop``
and that loop's parameters.  Each loop kind is a file of its own,
``bench/loops/<loop>.py``, found by that name, so a new kind of traffic is a
new file.  Its class ``Loop(traffic, part, A, dtype, seed, spans)`` builds
the program's operator from the partition and draws its inputs from the
seed, and has:

* ``op``: the built ``DistributedSpMV`` (its ``mesh`` and ``strategy``);
* ``window_spans``: the ``bench.*`` spans that bound the traced window;
* ``warm()``: every shape the window will use, once;
* ``run(seconds) -> Window``: the measured closed loop;
* ``probe()``: extra calls made after the window of a traced run;
* ``free()``: drop the program's device state before the check;
* ``check(limits) -> ({name: value}, failed)``: the answers against the
  float64 reference.

``Window.counters`` names what the window did (``solves``, ``iterations``,
``products``); the metric readers read those counters, never the loop's
name.  The program is reached through module attributes at call time, so a
test can put a broken program underneath and see the check fail.
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    calls: int
    elapsed_s: float  # window start to the end of the last call
    latencies_s: list
    counters: dict


def closed_loop(seconds: float, call) -> Window:
    """Call ``call(i)`` back to back until ``seconds`` have passed; the call
    running at the deadline completes and counts."""
    lat = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    end = t0
    i = 0
    while end < deadline:
        start = time.perf_counter()
        call(i)
        end = time.perf_counter()
        lat.append(end - start)
        i += 1
    return Window(calls=i, elapsed_s=end - t0, latencies_s=lat, counters={})
