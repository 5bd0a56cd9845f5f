"""The program's spans and scopes: what a profiler trace sees of it.

* :func:`span` marks host work.  It is a ``jax.profiler.TraceAnnotation``
  named ``repro.<name>``, which a profiler trace shows on the host's clock,
  and on exit it reports its duration on JAX's monitoring bus as the event
  ``/repro/<name>``, which any ``jax.monitoring`` duration listener
  receives.  With no profiler and no listener it costs a pair of clock
  reads and two no-op calls.
* :func:`scope` names device work.  It is ``jax.named_scope``: it labels the
  operations a traced function emits (their HLO ``op_name``, the ``tf_op``
  of each operation in a device trace) and costs nothing at run time.

Spans sit at host call sites, never inside a traced function; scopes sit
inside traced functions.  ``PERF.md`` lists every name in use with the
metric that reads it.
"""

from __future__ import annotations

import time

import jax

#: prefix of every host span's name in a profiler trace
SPAN_PREFIX = "repro."
#: prefix of every span's duration event on ``jax.monitoring``
EVENT_PREFIX = "/repro/"


class span:
    """Host span ``repro.<name>``; its duration goes to ``/repro/<name>``."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        jax.monitoring.record_event_duration_secs(EVENT_PREFIX + self.name, seconds)
        return False


def scope(name: str):
    """Device scope ``name`` over the operations traced inside it."""
    return jax.named_scope(name)
