"""The window, up to the end of the last product, over the products it
completed (host clock)."""


def read(run):
    products = run.window.counters.get("products")
    return 1e6 * run.window.elapsed_s / products if products else None
