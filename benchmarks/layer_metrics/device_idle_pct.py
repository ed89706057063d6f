"""Share of the traced window in which no operation ran on the busiest
device: 1 - union of operation intervals / window."""

LAYER = "device"
UNIT = "%"
MOVES = "query_s"
SOURCE = "device_trace"


def read(run):
    if not run.trace.busy or run.trace.window_s <= 0:
        return None
    busy = run.trace.busy[run.trace.busiest()]
    return 100.0 * (1.0 - busy / run.trace.window_s)
