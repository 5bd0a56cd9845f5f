"""Host seconds in the program's ``partition_csr``: the ``bench.partition``
span around it."""


def read(run):
    spans = run.spans.durations("bench.partition")
    return sum(spans) if spans else None
