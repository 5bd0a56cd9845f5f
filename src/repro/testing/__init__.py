"""Test-support utilities vendored with the library (no external deps)."""

from repro.testing.traces import ARRIVAL_PATTERNS, make_trace, zipf_weights

__all__ = [
    "ARRIVAL_PATTERNS",
    "make_trace",
    "zipf_weights",
]
