"""Tiled nested-loop (non-equi) join.

TPU-native replacement for the reference's nested-loop and interval
joins (reference: bodo/libs/_nested_loop_join_impl.cpp cross-product
block join, bodo/libs/_interval_join.cpp point-in-interval). The C++
row-pair loop becomes a tiled broadcast: probe rows are processed in
fixed-size tiles, each tile evaluates the join predicate on the dense
[tile x build] pair grid in one fused kernel (VPU-friendly elementwise
compare + compact), so device memory is O(tile x build), never
O(|L| x |R|). Matches are compacted to a bucketed output capacity with
a host-checked overflow retry (the same capacity discipline as the
shuffle buckets).

An interval fast path sorts the probe side by the point column and the
build side by interval start, so each probe tile only grids against the
build PREFIX whose starts precede the tile's max point — near-linear
for mostly-disjoint intervals, degrading gracefully to the full grid
under heavy overlap.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bodo_tpu.ops import kernels as K
from bodo_tpu.plan.expr import (BinOp, ColRef, Expr, eval_expr,
                                expr_columns)
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import Column, REP, Table, round_capacity

# pair-grid budget: tile_rows * build_cap <= this (elements per pred col)
_GRID_BUDGET = 1 << 22

from bodo_tpu.utils.kernel_cache import (KERNEL_CACHE_SIZE, KernelCache,
                                         named_jit)
from bodo_tpu.utils.tracing import host_read

_jit_cache = KernelCache(maxsize=KERNEL_CACHE_SIZE,
                         subsystem="nonequi")


def _pow2(n: int) -> int:
    c = 128
    while c < n:
        c <<= 1
    return c


def _build_tile_kernel(sig, pred_key, names_l: Tuple[str, ...],
                       names_r: Tuple[str, ...], pred: Expr,
                       schema, dicts, T: int, B: int, out_cap: int,
                       want_matched: bool):
    key = ("nljoin", sig, pred_key, T, B, out_cap, want_matched)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn

    def body(ltree, tcount, rtree, rcount):
        li = jnp.arange(T * B) // B
        ri = jnp.arange(T * B) % B
        grid: Dict[str, Tuple] = {}
        need = expr_columns(pred)
        for n in names_l:
            if n in need:
                d, v = ltree[n]
                grid[n] = (d[li], None if v is None else v[li])
        for n in names_r:
            if n in need:
                d, v = rtree[n]
                grid[n] = (d[ri], None if v is None else v[ri])
        mask, mv = eval_expr(pred, grid, dicts, schema)
        if mv is not None:
            mask = mask & mv
        mask = mask & (li < tcount) & (ri < rcount)
        (ci_l, ci_r), cnt = K.compact(mask, (li, ri), out_cap)
        out: Dict[str, Tuple] = {}
        for n in names_l:
            d, v = ltree[n]
            out[n] = (d[ci_l], None if v is None else v[ci_l])
        for n in names_r:
            d, v = rtree[n]
            out[n] = (d[ci_r], None if v is None else v[ci_r])
        if want_matched:
            matched = jax.ops.segment_max(
                mask.astype(jnp.int32), li, num_segments=T).astype(bool)
            return out, cnt, matched
        return out, cnt

    fn = named_jit("join_nonequi", body)
    _jit_cache[key] = fn
    return fn


def nl_join_rep(left: Table, right: Table, pred: Expr,
                how: str = "inner") -> Table:
    """Nested-loop join of two replicated tables under an arbitrary
    predicate over the COMBINED (already suffix-disambiguated) columns.
    how: inner | left. Output is REP with matches in probe-major order
    (then unmatched probe rows for how=left, pandas/SQL style)."""
    assert how in ("inner", "left"), how
    from bodo_tpu import relational as R
    left = R.shrink_to_fit(left)
    right = R.shrink_to_fit(right)
    B = max(right.capacity, 1)
    T = _pow2(max(min(left.capacity, max(_GRID_BUDGET // B, 1)), 1))
    # _sig fingerprints dictionaries too: string predicates bake the
    # host dictionary LUT into the trace, so same-shaped tables with
    # different dictionaries must not share a cached kernel
    sig = (R._sig(left), R._sig(right))
    schema = {n: c.dtype for n, c in left.columns.items()}
    schema.update({n: c.dtype for n, c in right.columns.items()})
    dicts = {n: c.dictionary for n, c in left.columns.items()
             if c.dictionary is not None}
    dicts.update({n: c.dictionary for n, c in right.columns.items()
                  if c.dictionary is not None})
    names_l = tuple(left.names)
    names_r = tuple(right.names)
    rtree = right.device_data()
    rcount = jnp.asarray(right.nrows)

    parts: List[Table] = []
    matched_host: List[np.ndarray] = []
    out_cap = _pow2(T)  # ~1 match per probe row to start
    n_tiles = max(1, -(-left.nrows // T)) if left.nrows else 0
    for ti in range(n_tiles):
        lo = ti * T
        tile_rows = min(T, left.nrows - lo)
        ltree = {}
        for n in names_l:
            c = left.columns[n]
            d = jax.lax.dynamic_slice_in_dim(c.data, lo, T) \
                if left.capacity >= lo + T else \
                jnp.pad(c.data[lo:], (0, T - (left.capacity - lo)))
            v = None
            if c.valid is not None:
                v = jax.lax.dynamic_slice_in_dim(c.valid, lo, T) \
                    if left.capacity >= lo + T else \
                    jnp.pad(c.valid[lo:], (0, T - (left.capacity - lo)))
            ltree[n] = (d, v)
        while True:
            fn = _build_tile_kernel(sig, pred.key(), names_l, names_r,
                                    pred, schema, dicts, T, B, out_cap,
                                    how == "left")
            res = fn(ltree, jnp.asarray(tile_rows), rtree, rcount)
            out, cnt = res[0], res[1]
            n_match = int(host_read("nonequi_count", cnt))
            if n_match <= out_cap:
                break
            out_cap = _pow2(n_match)
        if how == "left":
            m = np.asarray(host_read("nonequi_matched", res[2]))[:tile_rows]
            matched_host.append(m)
        cols: Dict[str, Column] = {}
        for n in names_l:
            src = left.columns[n]
            d, v = out[n]
            cols[n] = Column(d, v, src.dtype, src.dictionary)
        for n in names_r:
            src = right.columns[n]
            d, v = out[n]
            cols[n] = Column(d, v, src.dtype, src.dictionary)
        parts.append(Table(cols, n_match, REP, None))

    if not parts:
        combined = {}
        for n in names_l:
            c = left.columns[n]
            combined[n] = c
        for n in names_r:
            combined[n] = right.columns[n]
        base = Table(combined, 0, REP, None)
        out = base
    elif len(parts) == 1:
        out = parts[0]
    else:
        out = R.concat_tables(parts)

    if how == "left":
        unmatched = ~np.concatenate(matched_host) if matched_host \
            else np.ones(left.nrows, dtype=bool)
        if unmatched.any():
            idx = np.flatnonzero(unmatched)
            pad = _null_padded_left_rows(left, right, idx)
            out = R.concat_tables([out, pad]) if out.nrows else pad
    return R.shrink_to_fit(out) if out.nrows else out


def _null_padded_left_rows(left: Table, right: Table,
                           idx: np.ndarray) -> Table:
    """Unmatched probe rows with all-null build columns (left join)."""
    n = len(idx)
    cap = round_capacity(max(n, 1))
    gi = jnp.asarray(np.pad(idx, (0, cap - n)))
    cols: Dict[str, Column] = {}
    for name, c in left.columns.items():
        d = c.data[gi]
        v = None if c.valid is None else c.valid[gi]
        cols[name] = Column(d, v, c.dtype, c.dictionary)
    for name, c in right.columns.items():
        z = jnp.zeros((cap,), dtype=c.data.dtype)
        cols[name] = Column(z, jnp.zeros((cap,), bool), c.dtype,
                            c.dictionary)
    return Table(cols, n, REP, None)


# ---------------------------------------------------------------------------
# interval fast path
# ---------------------------------------------------------------------------

def match_interval_pattern(pred: Expr, left_cols, right_cols
                           ) -> Optional[Tuple[str, str]]:
    """Detect a point-in-interval conjunct pair: (p >= lo & p <= hi)
    with p from the probe side and lo/hi from the build side (any
    operand order / strictness). Returns (probe_col, build_lo_col) for
    band pruning, or None."""
    conj: List[Expr] = []

    def flat(e):
        if isinstance(e, BinOp) and e.op == "&":
            flat(e.left)
            flat(e.right)
        else:
            conj.append(e)
    flat(pred)
    lower = None  # (p, lo): p >= lo
    upper = None  # (p, hi): p <= hi
    for e in conj:
        if not (isinstance(e, BinOp) and e.op in (">", ">=", "<", "<=")
                and isinstance(e.left, ColRef)
                and isinstance(e.right, ColRef)):
            continue
        a, b, op = e.left.name, e.right.name, e.op
        if op in ("<", "<="):
            a, b = b, a  # normalize to a >= b / a > b
        # now a (>|>=) b
        if a in left_cols and b in right_cols:
            lower = (a, b)
        elif b in left_cols and a in right_cols:
            upper = (b, a)
    if lower and upper and lower[0] == upper[0]:
        return lower[0], lower[1]
    return None


def nl_join_interval(left: Table, right: Table, pred: Expr,
                     probe_col: str, lo_col: str,
                     how: str = "inner") -> Table:
    """Band-pruned nested-loop join: probe sorted by the point column,
    build sorted by interval start; each probe tile only grids against
    build rows whose start <= the tile's max point (a build prefix).
    Full predicate still evaluated on the pruned grid, so correctness
    never depends on the pruning (reference: the sort-based interval
    join, bodo/libs/_interval_join.cpp)."""
    from bodo_tpu import relational as R
    if left.column(probe_col).valid is not None or \
            right.column(lo_col).valid is not None:
        # null sort keys carry sentinel physical values, breaking the
        # monotone-prefix pruning invariant — full grid instead
        return nl_join_rep(left, right, pred, how)
    left_s = R.sort_table(R.shrink_to_fit(left), [probe_col])
    right_s = R.sort_table(R.shrink_to_fit(right), [lo_col])
    # host copy of the sort columns to size each tile's build prefix
    p_host, lo_host = host_read("nonequi_bounds", (
        left_s.column(probe_col).data, right_s.column(lo_col).data))
    p_host = np.asarray(p_host)[:left_s.nrows]
    lo_host = np.asarray(lo_host)[:right_s.nrows]
    B_full = max(right_s.nrows, 1)
    T = _pow2(max(min(left_s.capacity, max(_GRID_BUDGET // B_full, 1)),
                  1))
    parts: List[Table] = []
    n_tiles = max(1, -(-left_s.nrows // T)) if left_s.nrows else 0
    for ti in range(n_tiles):
        lo_r = ti * T
        tile_rows = min(T, left_s.nrows - lo_r)
        pmax = p_host[lo_r:lo_r + tile_rows].max()
        # build prefix: rows with start <= pmax
        c1 = int(np.searchsorted(lo_host, pmax, side="right"))
        bcap = _pow2(max(c1, 1))
        tile = _slice_rep(left_s, lo_r, T, tile_rows)
        prefix = _slice_rep(right_s, 0, bcap, min(c1, right_s.nrows))
        # per-tile left join is globally correct: tiles partition the
        # probe rows, so each tile null-pads its own unmatched rows
        parts.append(nl_join_rep(tile, prefix, pred, how))
    if not parts:
        return nl_join_rep(left_s, right_s, pred, how)
    out = parts[0] if len(parts) == 1 else R.concat_tables(
        [p for p in parts if p.nrows] or parts[:1])
    return out


def _slice_rep(t: Table, off: int, cap: int, rows: int) -> Table:
    cols: Dict[str, Column] = {}
    for n, c in t.columns.items():
        end = min(off + cap, c.capacity)
        d = c.data[off:end]
        if d.shape[0] < cap:
            d = jnp.pad(d, (0, cap - d.shape[0]))
        v = None
        if c.valid is not None:
            v = c.valid[off:end]
            if v.shape[0] < cap:
                v = jnp.pad(v, (0, cap - v.shape[0]))
        cols[n] = Column(d, v, c.dtype, c.dictionary)
    return Table(cols, rows, REP, None)
