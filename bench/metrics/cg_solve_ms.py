"""Time to solution: the window, up to the end of the last solve, over the
solves it completed (host clock)."""


def read(run):
    solves = run.window.counters.get("solves")
    return 1e3 * run.window.elapsed_s / solves if solves else None
