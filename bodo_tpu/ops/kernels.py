"""Shared kernel utilities: padding masks, null handling, compaction.

Replaces the reference's C++ array utilities (bodo/libs/_array_utils.cpp,
_array_build_buffer.cpp) with jit-traceable equivalents. All kernels obey
the padded-capacity convention: arrays are fixed-capacity, the first
`count` rows are real, the rest is padding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp


def row_mask(count, capacity: int):
    """Boolean mask of real (non-padding) rows."""
    return jnp.arange(capacity) < count


def value_ok(data, valid, padmask):
    """Mask of rows whose value participates in aggregation:
    real row AND not null (explicit mask or float NaN)."""
    ok = padmask
    if valid is not None:
        ok = ok & valid
    if jnp.issubdtype(data.dtype, jnp.floating):
        ok = ok & ~jnp.isnan(data)
    return ok


def compact_index(mask, capacity_out: Optional[int] = None):
    """Where a stable compaction's rows come from: `(src, n)` with
    `src` int32[capacity_out] holding the ascending positions of the
    rows where `mask` is True and `n` their count. Past `n` (and past
    the mask's rows when `capacity_out` exceeds them) `src` is 0, so
    `a[src]` needs no guard, only `take_compacted`'s zero fill.

    One int32 scatter of the row numbers: 4.7-6.1 ns a slot of the mask
    on the v5e whatever is kept and whatever `capacity_out`; a
    `searchsorted` over the cumsum was slower at every point of the
    sweep (`chip_compact_sweep.py`; the table is in PERF.md section 6,
    PR 34)."""
    cap = mask.shape[0]
    out_cap = cap if capacity_out is None else capacity_out
    # each kept row's output position; `out_cap` (dropped) elsewhere
    pos = jnp.where(mask, jnp.cumsum(mask, dtype=jnp.int32) - 1, out_cap)
    src = jnp.zeros((out_cap,), jnp.int32).at[pos].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    return src, jnp.sum(mask)


def compact(mask, arrays: Tuple, capacity_out: Optional[int] = None):
    """Stable-compact rows where `mask` is True to the front.

    Returns (compacted arrays, new_count). Rows past new_count are
    zeroed, `None` entries pass through, trailing dimensions are kept,
    rows past `capacity_out` are dropped. This is the workhorse for
    filters and shuffle-receive cleanup — the analogue of the
    reference's RetrieveTable/filter paths (bodo/libs/_array_utils.cpp).

    The surviving rows' source index is found once (`compact_index`)
    and every array gathered at it, at the size of the output: on the
    v5e a scatter of one 64-bit column costs 68-81 ns a slot of the
    INPUT, the index 4.7-6.1 ns a slot and a gathered 64-bit column
    13-15 ns a row of the OUTPUT, so index-and-gather is ahead from
    the first 64-bit column (3.8 times at one, 5.0-5.6 at eleven, far
    more where the output is smaller than the input;
    `chip_compact_sweep.py`, PERF.md section 6, PR 34). One form for
    every input: handed no array, the index is dead code under jit."""
    src, n = compact_index(mask, capacity_out)
    return take_compacted(src, n, arrays), n


def take_compacted(src, n, arrays: Tuple):
    """Rows `src` (a `compact_index`) of each array, zero past `n`."""
    keep = row_mask(n, src.shape[0])
    outs = []
    for a in arrays:
        if a is None:
            outs.append(None)
            continue
        k = keep.reshape(keep.shape + (1,) * (a.ndim - 1))
        outs.append(jnp.where(k, a[src], jnp.zeros((), a.dtype)))
    return tuple(outs)


def gather_rows(perm, arrays: Tuple):
    """Apply a row permutation/selection index to several arrays."""
    return tuple(None if a is None else a[perm] for a in arrays)


def fill_null(data, valid, fill):
    """Replace null slots with `fill` (for min/max identity values)."""
    if valid is None:
        if jnp.issubdtype(data.dtype, jnp.floating):
            return jnp.where(jnp.isnan(data), fill, data)
        return data
    return jnp.where(valid, data, fill)
