"""Mean iterations per solve in the window (``SolveResult.iterations``)."""

import numpy as np


def read(run):
    its = run.window.counters.get("iterations")
    return float(np.mean(its)) if its else None
