"""Device milliseconds per query in the hash shuffle's program family
(`jit_shuffle_by_key`: destinations hashed from the keys, rows packed
into a bucket a shard, `all_to_all`, the received rows compacted) on
the busiest device. Where the engine says how its joins crossed chips
(`bodo:exchange.` spans) and no shuffle program ran, 0 is a reading;
where it says nothing and none ran, there is nothing to read."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "distribution"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"shuffle_by_key"]
SAYS = r"bodo:exchange\."


def read(run):
    seconds = run.trace.family_seconds(PATTERNS)
    if seconds is None and span_seconds(run.trace, SAYS) is not None:
        seconds = 0.0
    return per_query(run, seconds, 1e3)
