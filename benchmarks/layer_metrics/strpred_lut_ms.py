"""Host milliseconds per query spent building a string predicate's
look-up table over a column's dictionary: the union of the
`bodo:strpred.lut` spans (`plan/expr.py eval_expr`, a Python loop over
the dictionary while a program is traced). A compiled program holds the
table as a constant, so a repeat of a query builds none and this reads
0.0, as `compiles_in_window` reads 0; the day every query rebuilds a
table as long as `part` it reads tens of milliseconds. A program that
writes neither `bodo:join.` nor `bodo:strpred.` spans (a commit before
them) gives nothing to read."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "plan"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:strpred\.lut$"
WRITES_THEM = r"bodo:(join|strpred)\."


def read(run):
    if span_seconds(run.trace, WRITES_THEM) is None:
        return None
    return per_query(run, span_seconds(run.trace, SPANS) or 0.0, 1e3)
