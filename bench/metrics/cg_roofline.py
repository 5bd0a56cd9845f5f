"""Share of the CG roofline, the mean over chips: each chip's floor of one
textbook CG iteration with every vector pass fused, over its rows, nonzeros
and halo (:func:`bench.roofline.cg_iteration_work`), over its device busy
time per iteration in the traced window."""

from bench import roofline


def read(run):
    its = run.window.counters.get("iterations")
    if run.trace is None or not its:
        return None
    shares = []
    for work, dev in zip(run.work, run.rank_devices):
        busy = run.trace.busy_s.get(dev)
        if not busy:
            return None
        work_per_iteration = roofline.cg_iteration_work(work.rows, work.nnz, work.halo)
        floor, _ = roofline.floor_seconds(*work_per_iteration, run.peaks)
        shares.append(100.0 * floor * sum(its) / busy)
    return sum(shares) / len(shares)
