"""Bytes a join's hash shuffle has to carry between chips, from the rows
it was given and the carried columns at the source schema's widths
(`harness/roofline.py WIDTH`), and the least time the chips' interconnect
could take. The count is of the work: bucket padding, null masks and
the rows a shard keeps for itself are not, so a fuller bucket or a
leaner exchange raises the share the reader makes of it."""

import re

from .roofline import WIDTH, peaks

# the planner's name for a source column: `t<n>__<column>`
_SOURCE = re.compile(r"^t\d+__(.+)$")
# a carried column the query's file does not list: an int64 or float64
OTHER_WIDTH = 8


def source_widths(reads):
    """{column: bytes} of every column a query's file lists."""
    return {c: WIDTH[ty] for cols in reads.values() for c, ty in cols.items()}


def shuffle_bytes(rows, columns, widths, chips):
    """rows x the carried columns' source widths x (chips - 1) / chips:
    of rows hashed uniformly, that share lands on another chip."""
    row = 0
    for name in columns:
        m = _SOURCE.match(name)
        row += widths.get(m.group(1) if m else name, OTHER_WIDTH)
    return rows * row * (chips - 1) / chips


def least_seconds(nbytes, chips, device_kind):
    """The interconnect's time for `nbytes` spread over the chips, each
    sending its share at its whole `ici_gbit_per_s` (`peaks.json`)."""
    return nbytes / chips / (peaks(device_kind)["ici_gbit_per_s"] * 1e9 / 8)
