"""Share of the product's roofline, the mean over chips: each chip's floor
(:class:`bench.roofline.RankWork`: its values, x slice, halo and w slice
over HBM bandwidth) over its device busy time per product in the traced
window."""

from bench import roofline


def read(run):
    products = run.window.counters.get("products")
    if run.trace is None or not products:
        return None
    shares = []
    for work, dev in zip(run.work, run.rank_devices):
        busy = run.trace.busy_s.get(dev)
        if not busy:
            return None
        floor, _ = roofline.floor_seconds(work.flops, work.bytes, run.peaks)
        shares.append(100.0 * floor * products / busy)
    return sum(shares) / len(shares)
