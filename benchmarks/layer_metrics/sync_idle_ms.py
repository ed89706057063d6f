"""Milliseconds per query in which the busiest device sat idle waiting on
a host read: the idle gaps (the complement, inside the traced window, of
the union of the device's `XLA Ops` intervals) inside which a
`bodo:sync.<site>` span ends. Such a read drains the device's queue, so
the gap lasts until the host has used the value and dispatched again.
Only the part of a gap before a `bodo:to_pandas`, `bodo:plan.` or
`bench:frontend` span is open is counted: `result_host_ms`,
`plan_host_ms` and `frontend_ms` read those. Where the engine writes any
`bodo:sync.` or `bodo:exec.` span, 0 is a reading; a program that
writes neither (a commit before them) gives nothing to read."""

import re

import numpy as np

from harness.readers import per_query
from harness.trace import union_ns

LAYER = "device"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SYNC = "bodo:sync."
WRITTEN = ("bodo:sync.", "bodo:exec.")
READ_ELSEWHERE = re.compile(r"bodo:to_pandas$|bodo:plan\.|bench:frontend$")


def read(run):
    tr = run.trace
    names, starts, ends = tr.host
    w0, w1 = tr.window_ns
    names = [str(n) for n in names]
    if not any(w0 <= s < w1 and n.startswith(WRITTEN)
               for n, s in zip(names, starts)):
        return None
    if not tr.busy:
        return None
    ops = tr.devices[tr.busiest()]["ops"]
    _, busy = union_ns(*tr._clip(ops[1], ops[2]))
    edges = np.concatenate([[w0], busy.ravel(), [w1]])
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    sync_ends = np.sort([e for n, e in zip(names, ends)
                         if n.startswith(SYNC)])
    other = [i for i, n in enumerate(names) if READ_ELSEWHERE.match(n)]
    o_s, o_e = starts[other], ends[other]
    idle = 0.0
    for a, b in gaps:
        # up to the first instant at which another reader's span is open
        opens = o_s[(o_s < b) & (o_e > a)]
        cut = min(b, max(a, opens.min())) if len(opens) else b
        lo = np.searchsorted(sync_ends, a, side="left")
        if lo < len(sync_ends) and sync_ends[lo] <= cut and cut > a:
            idle += cut - a
    return per_query(run, idle / 1e9, 1e3)
