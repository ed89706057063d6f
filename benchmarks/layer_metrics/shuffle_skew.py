"""How unevenly a join's hash shuffle leaves its rows: the largest
shard's rows over the mean, after each `shuffle_by_key` of a join
(inside a `bodo:exchange.shuffle` span) that starts in the traced
window, the largest of them. 1.0 is an even spread; on four chips 4.0
is every row on one. Read from `rows_max` and `rows_mean` on the
shuffle's span; a program that writes neither (a commit before them)
gives nothing to read."""

from harness import span_args

LAYER = "distribution"
UNIT = "ratio"
MOVES = "query_s"
SOURCE = "program_span"


def read(run):
    shuffles = span_args.join_shuffles(run.trace)
    if shuffles is None:
        return None
    return max((float(a["rows_max"]) / float(a["rows_mean"])
                for a in shuffles if float(a["rows_mean"]) > 0),
               default=None)
