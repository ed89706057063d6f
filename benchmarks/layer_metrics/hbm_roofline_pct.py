"""The least time the chip's memory needs to read the query's input once,
as a share of the busiest device's busy time per query.

Bytes: rows x the widths of the columns the query's file lists, at the
source schema's widths (`harness/roofline.py`), divided over the chips;
peak from `peaks.json` by device kind. The count is of the work: a kernel
replaced, fused or deleted leaves it as it is. Nothing to read off a TPU.
"""

from harness import roofline

LAYER = "kernels"
UNIT = "%"
MOVES = "query_s"
SOURCE = "device_trace"


def read(run):
    if run.trace.platform != "tpu" or not run.traced_queries:
        return None
    busy = run.trace.busy[run.trace.busiest()] / run.traced_queries
    if busy <= 0:
        return None
    least = roofline.least_seconds(run.bytes_per_query, run.chips,
                                   run.device_kind)
    return 100.0 * least / busy
