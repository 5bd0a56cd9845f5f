"""Median host time of the exchange alone, ``halo(v)`` to
``block_until_ready``, over the ``bench.exchange`` spans of a traced run."""

import numpy as np


def read(run):
    spans = run.spans.durations("bench.exchange")
    return 1e6 * float(np.median(spans)) if spans else None
