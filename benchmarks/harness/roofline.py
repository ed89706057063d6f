"""Bytes a query has to read once, from the columns its file lists at the
source schema's widths, and the least time the chip's memory could take.
The count is of the work, not of the implementation: a kernel replaced,
fused or deleted leaves it as it is."""

import json
import os

from .spec import BENCH_DIR

# bytes a value takes in the source schema; a string column counts as its
# dictionary code
WIDTH = {"int64": 8, "float64": 8, "timestamp": 8, "string": 4, "int32": 4,
         "float32": 4, "bool": 1}


def peaks(device_kind):
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json; it has {sorted(table)}")
    return table[device_kind]


def query_bytes(reads, rows):
    """reads: {table: {column: type}}; rows: {table: row count}."""
    return sum(rows[t] * sum(WIDTH[ty] for ty in cols.values())
               for t, cols in reads.items())


def least_seconds(nbytes, chips, device_kind):
    return nbytes / chips / (peaks(device_kind)["hbm_gb_per_s"] * 1e9)
