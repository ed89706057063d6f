"""How many of a query's joins still go down the sort route (`_join_rep`,
`_join_sharded`, `_join_broadcast`: `join_local` and its sorts): the
`bodo:join.sort` spans that start inside the traced window, per traced
query. The engine opens one span a join around the realisation it took
(`bodo:join.dense`, `.hash`, `.sort`, `.fused`); a count is a count, so
where it writes any of them and none is a sort, 0 is a reading. A
program that writes no `bodo:join.` span (a commit before them) gives
nothing to read."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_span"
ROUTES = "bodo:join."
SORT = "bodo:join.sort"


def read(run):
    names, starts, _ = run.trace.host
    w0, w1 = run.trace.window_ns
    routes = [str(n) for n, s in zip(names, starts)
              if w0 <= s < w1 and str(n).startswith(ROUTES)]
    if not routes:
        return None
    return per_query(run, routes.count(SORT))
