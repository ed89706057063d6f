"""Device milliseconds per query in collective operations (all-to-all,
all-gather, all-reduce, reduce-scatter, collective-permute) on the
busiest device. Nothing to read on one chip."""

from harness.readers import per_query

LAYER = "distribution"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "device_trace"


def read(run):
    return per_query(run, run.trace.collective_seconds(), 1e3)
