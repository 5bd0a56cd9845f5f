"""Set-up: from the moment the data exists on the host to the first warm
product or solve: partition, plan, placement, compile or cache load, and
warm-up (host clock).  Generating the data is logged apart."""


def read(run):
    return run.setup_s
