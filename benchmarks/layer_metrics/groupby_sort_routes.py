"""How many of a query's group-bys go down the sort route
(`ops/groupby.py groupby_local`: every row sorted by the keys, then
segment aggregates): the `bodo:groupby.sort` spans that start inside the
traced window, per traced query. The engine opens one span a group-by
around the realisation it took (`bodo:groupby.dense`, `.packed`,
`.hashed`, `.sort`, `.fused`); a count is a count, so where it writes any
of them and none is a sort, 0 is a reading. A program that writes no
`bodo:groupby.` span (a commit before them) gives nothing to read."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_span"
ROUTES = "bodo:groupby."
SORT = "bodo:groupby.sort"


def read(run):
    names, starts, _ = run.trace.host
    w0, w1 = run.trace.window_ns
    routes = [str(n) for n, s in zip(names, starts)
              if w0 <= s < w1 and str(n).startswith(ROUTES)]
    if not routes:
        return None
    return per_query(run, routes.count(SORT))
