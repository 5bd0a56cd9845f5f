"""95th percentile of per-call latency, dispatch to ``block_until_ready``,
over every product in the window (host clock)."""

import numpy as np


def read(run):
    if not run.window.counters.get("products"):
        return None
    return 1e6 * float(np.percentile(run.window.latencies_s, 95))
