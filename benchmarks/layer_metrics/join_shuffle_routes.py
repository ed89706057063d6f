"""How many of a query's joins move both sides between chips by hash
shuffle: the `bodo:exchange.shuffle` spans that start inside the traced
window, per traced query. On a mesh the engine opens one span a join
around what carries its rows across chips (`bodo:exchange.shuffle`
around the two `shuffle_by_key` of a join of sharded sides,
`bodo:exchange.broadcast` around the replication of a build side); a
count is a count, so where it writes any of them and none is a shuffle,
0 is a reading. A program that writes no `bodo:exchange.` span (a
commit before them) gives nothing to read."""

from harness.readers import per_query

LAYER = "distribution"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_span"
EXCHANGES = "bodo:exchange."
SHUFFLE = "bodo:exchange.shuffle"


def read(run):
    names, starts, _ = run.trace.host
    w0, w1 = run.trace.window_ns
    seen = [str(n) for n, s in zip(names, starts)
            if w0 <= s < w1 and str(n).startswith(EXCHANGES)]
    if not seen:
        return None
    return per_query(run, seen.count(SHUFFLE))
