"""How many times a query stopped the device to read a value back: the
`bodo:sync.<site>` spans that start inside the traced window, per traced
query. The engine reads every count, flag and key range it decides its
next step by through one entry point (`utils/tracing.py host_read`),
which writes that span; the answer's own copy (`bodo:to_pandas`) is not
one. A count is a count, so where the engine writes any `bodo:sync.` or
`bodo:exec.` span, 0 is a reading. A program that writes neither (a
commit before them) gives nothing to read."""

from harness.readers import per_query

LAYER = "plan"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_span"
SYNC = "bodo:sync."
WRITTEN = ("bodo:sync.", "bodo:exec.")


def read(run):
    names, starts, _ = run.trace.host
    w0, w1 = run.trace.window_ns
    seen = [str(n) for n, s in zip(names, starts)
            if w0 <= s < w1 and str(n).startswith(WRITTEN)]
    if not seen:
        return None
    return per_query(run, sum(n.startswith(SYNC) for n in seen))
