"""Device idle share of the traced window of a solve loop, the mean over
chips: 1 - (union of operation intervals / window)."""


def read(run):
    if run.trace is None or not run.window.counters.get("solves"):
        return None
    return 100.0 * run.trace.mean_idle_share()
