"""The tail of the whole query as the front door's caller sees it: the
95th percentile of the times of all queries of the window, in a window
that holds 200 queries or more (ten samples or more beyond it). Up to
PR 29 this was the end-to-end metric `query_p95_s`; in a closed loop of
one query it spreads as the host's jitter does (PERF.md, section 2), so
it stands here without a bound until an open-loop mix brings a tail that
says more than the mean."""

import numpy as np

LAYER = "entry"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "host_clock"

MIN_QUERIES = 200


def read(run):
    if len(run.latencies) < MIN_QUERIES:
        return None
    return 1e3 * float(np.percentile(run.latencies, 95))
