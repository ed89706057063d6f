"""The least time the chips' interconnect needs for the rows a query's
joins hash-shuffle, as a share of the busiest device's time in the
shuffle's programs (`jit_shuffle_by_key`) over the traced window.

Bytes (`harness/ici.py`): for every `shuffle_by_key` of a join (inside a
`bodo:exchange.shuffle` span), the rows it was given (`rows` on its
span) x the widths of the columns it carried (`columns`) at the source
schema x (chips - 1) / chips; peak `ici_gbit_per_s` from `peaks.json`.
Padding is not work: the `all_to_all` carries every bucket at a shard's
whole capacity, so a fuller bucket raises the share. Nothing to read off
a TPU, or from a program that writes no records on its shuffle spans (a
commit before them)."""

from harness import ici, span_args

LAYER = "distribution"
UNIT = "%"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"shuffle_by_key"]


def read(run):
    if run.trace.platform != "tpu" or not run.traced_queries:
        return None
    device = run.trace.family_seconds(PATTERNS)
    shuffles = span_args.join_shuffles(run.trace)
    if not device or shuffles is None:
        return None
    widths = {}
    for q in run.cell.queries.values():
        widths.update(ici.source_widths(q.reads))
    nbytes = sum(ici.shuffle_bytes(int(a["rows"]),
                                   str(a["columns"]).split(), widths,
                                   run.chips) for a in shuffles)
    return 100.0 * ici.least_seconds(nbytes, run.chips,
                                     run.device_kind) / device
