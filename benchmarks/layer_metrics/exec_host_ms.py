"""Host milliseconds per query in the executor's own work between
operators: the union of `bodo:exec.*` spans per traced query.
`bodo:exec.observe` is `plan/physical.py _exec`'s bookkeeping before a
node (result-cache lookup, EXPLAIN snapshots) and after it (EXPLAIN
record, AQE and elastic observations, result-cache record);
`bodo:exec.replan` is the run-time re-plan of an inner join chain once
its leaves have run (`plan/adaptive.py maybe_reoptimize_join`). Where
the engine writes any `bodo:sync.` or `bodo:exec.` span, 0 is a reading;
a program that writes neither (a commit before them) gives nothing to
read."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "plan"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:exec\."
WRITTEN = ("bodo:sync.", "bodo:exec.")


def read(run):
    names, starts, _ = run.trace.host
    w0, w1 = run.trace.window_ns
    if not any(w0 <= s < w1 and str(n).startswith(WRITTEN)
               for n, s in zip(names, starts)):
        return None
    return per_query(run, span_seconds(run.trace, SPANS) or 0.0, 1e3)
