"""Window kernels: cumulative ops, rolling windows, shift/diff.

TPU-native replacement for the reference's parallel window machinery
(bodo/hiframes/rolling.py halo exchange via bodo.libs.parallel_ops,
bodo/libs/window/*.cpp, dist_cumsum via MPI_Exscan
bodo/libs/distributed_api.py:2205). Cross-shard state rides collectives:
cumulative offsets via exscan (all_gather + masked reduce), rolling halos
via lax.ppermute ring shifts (SURVEY.md §5 long-context analogue — the
ring-attention-style blockwise pass applied to windowed aggregation).

All kernels are local-block functions taking (x, valid, count) plus the
cross-shard carry; the shard_map wrapper lives in relational.py.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bodo_tpu.ops import kernels as K
from bodo_tpu.parallel import collectives
from bodo_tpu.utils.kernel_cache import bounded_jit


def _ok(x, valid, padmask):
    return K.value_ok(x, valid, padmask)


# ---------------------------------------------------------------------------
# cumulative ops: local part + carry combine
# ---------------------------------------------------------------------------

_CUM_NEUTRAL = {"cumsum": 0.0, "cumprod": 1.0,
                "cummax": -np.inf, "cummin": np.inf}


def cum_local(op: str, x, valid, count):
    """Returns (local result, local carry scalar). Result positions of
    null rows are NaN (pandas semantics); padding rows are neutral."""
    cap = x.shape[0]
    padmask = K.row_mask(count, cap)
    ok = _ok(x, valid, padmask)
    xf = x.astype(jnp.float64)
    if op == "cumsum":
        base = jnp.where(ok, xf, 0.0)
        loc = jnp.cumsum(base)
        carry = loc[-1]
    elif op == "cumprod":
        base = jnp.where(ok, xf, 1.0)
        loc = jnp.cumprod(base)
        carry = loc[-1]
    elif op == "cummax":
        base = jnp.where(ok, xf, -jnp.inf)
        loc = lax.cummax(base)
        carry = loc[-1]
    elif op == "cummin":
        base = jnp.where(ok, xf, jnp.inf)
        loc = lax.cummin(base)
        carry = loc[-1]
    else:
        raise ValueError(op)
    return loc, carry


def cum_combine(op: str, loc, carry_prefix):
    """Apply the exscan'd prefix carry from earlier shards."""
    if op == "cumsum":
        return loc + carry_prefix
    if op == "cumprod":
        return loc * carry_prefix
    if op == "cummax":
        return jnp.maximum(loc, carry_prefix)
    if op == "cummin":
        return jnp.minimum(loc, carry_prefix)
    raise ValueError(op)


def cum_carry_exscan(op: str, carry, axis: str):
    """Exclusive scan of carries over shards (identity for shard 0)."""
    n = collectives.axis_size(axis)
    idx = lax.axis_index(axis)
    gathered = lax.all_gather(carry, axis)          # [S]
    mask = jnp.arange(n) < idx
    ident = _CUM_NEUTRAL[op]
    vals = jnp.where(mask, gathered, ident)
    if op == "cumsum":
        return jnp.sum(vals)
    if op == "cumprod":
        return jnp.prod(vals)
    if op == "cummax":
        return jnp.max(vals)
    if op == "cummin":
        return jnp.min(vals)
    raise ValueError(op)


def cum_finalize(op: str, combined, x, valid, count):
    """NaN at null positions, zeros at padding."""
    cap = x.shape[0]
    padmask = K.row_mask(count, cap)
    ok = _ok(x, valid, padmask)
    return jnp.where(ok, combined, jnp.where(padmask, jnp.nan, 0.0))


# ---------------------------------------------------------------------------
# rolling windows (fixed window w, min_periods = w — pandas default)
# ---------------------------------------------------------------------------

def rolling_local(op: str, window: int, x, valid, count, halo_x, halo_ok,
                  global_offset):
    """Rolling over the local block with a (window-1)-row halo from the
    previous shard. halo_x/halo_ok: [window-1] values/validity from the
    end of the previous shard's real rows; global_offset: number of real
    rows before this shard (positions < window-1 globally are NaN)."""
    cap = x.shape[0]
    w = window
    padmask = K.row_mask(count, cap)
    ok = _ok(x, valid, padmask)
    xf = jnp.where(ok, x.astype(jnp.float64), 0.0)
    ext = jnp.concatenate([jnp.where(halo_ok, halo_x, 0.0), xf])
    ext_ok = jnp.concatenate([halo_ok, ok])

    if op in ("sum", "mean"):
        cs = jnp.cumsum(ext)
        cs0 = jnp.concatenate([jnp.zeros(1), cs])
        out = cs0[w:] - cs0[:-w]          # [cap]: sum over ext[i..i+w-1]
    elif op in ("min", "max"):
        # sparse-table doubling: O(log w) shifted reductions instead of an
        # O(w) unroll (which explodes trace size for large windows)
        ident = jnp.inf if op == "min" else -jnp.inf
        red = jnp.minimum if op == "min" else jnp.maximum
        level = jnp.where(ext_ok, ext, ident)
        span = 1
        while span * 2 <= w:
            level = red(level, jnp.concatenate(
                [level[span:], jnp.full((span,), ident)]))
            span *= 2
        # window [i, i+w) = block [i, i+span) ∪ block [i+w-span, i+w)
        lead = jnp.concatenate([level[w - span:],
                                jnp.full((w - span,), ident)]) \
            if w > span else level
        out = red(level, lead)[:cap]
    elif op == "count":
        cs = jnp.cumsum(ext_ok.astype(jnp.float64))
        cs0 = jnp.concatenate([jnp.zeros(1), cs])
        out = cs0[w:] - cs0[:-w]
    else:
        raise ValueError(op)

    okc = jnp.cumsum(ext_ok.astype(jnp.int64))
    okc0 = jnp.concatenate([jnp.zeros(1, jnp.int64), okc])
    nvalid = okc0[w:] - okc0[:-w]
    if op == "mean":
        out = out / jnp.maximum(nvalid, 1)
    gpos = global_offset + jnp.arange(cap)
    full = (nvalid == w) & (gpos >= w - 1) & padmask
    if op == "count":
        # pandas >= 1.3: count obeys min_periods=window like other aggs
        full_pos = (gpos >= w - 1) & padmask
        return jnp.where(full_pos, out, jnp.where(padmask, jnp.nan, 0.0))
    return jnp.where(full, out, jnp.where(padmask, jnp.nan, 0.0))


def tail_rows(x, valid, count, k: int):
    """Last k real rows of the block (for the halo send): values + ok."""
    cap = x.shape[0]
    idx = jnp.clip(count - k + jnp.arange(k), 0, cap - 1)
    have = (count - k + jnp.arange(k)) >= 0
    padmask = K.row_mask(count, cap)
    ok = _ok(x, valid, padmask)
    return (jnp.where(have, x.astype(jnp.float64)[idx], 0.0),
            have & ok[idx])


def multi_hop_halo(x, valid, count, k: int, axis: str):
    """Last k rows across ALL predecessor shards (not just the immediate
    neighbour): every shard all-gathers its k-row tail, and each shard
    selects the trailing k rows among shards before it. Row EXISTENCE
    (position past padding) is tracked separately from value validity —
    a null predecessor row still occupies its halo slot so shift/rolling
    see its null, exactly as a local previous row would. Handles short
    and empty predecessor shards — the case that used to force a gather
    fallback. Cost: one all_gather of [S, k] doubles + flags."""
    cap = x.shape[0]
    idx = jnp.clip(count - k + jnp.arange(k), 0, cap - 1)
    exists = (count - k + jnp.arange(k)) >= 0          # row present
    padmask = K.row_mask(count, cap)
    okv = _ok(x, valid, padmask)
    tx = jnp.where(exists, x.astype(jnp.float64)[idx], 0.0)
    tok = exists & okv[idx]                            # value also valid
    all_tx = lax.all_gather(tx, axis)                  # [S, k]
    all_tex = lax.all_gather(exists, axis)
    all_tok = lax.all_gather(tok, axis)
    S = all_tx.shape[0]
    r = lax.axis_index(axis)
    shard_ids = jnp.repeat(jnp.arange(S), k)     # [S*k], shard of each row
    flat_x = all_tx.reshape(-1)
    flat_ex = all_tex.reshape(-1) & (shard_ids < r)
    flat_ok = all_tok.reshape(-1) & (shard_ids < r)
    # j-th existing row counted from the END goes to halo slot k - j
    rev = jnp.cumsum(flat_ex[::-1])[::-1]
    slot = jnp.where(flat_ex & (rev <= k), k - rev, k)  # k = dropped
    halo_x = jnp.zeros(k, flat_x.dtype).at[slot].set(flat_x, mode="drop")
    halo_ok = jnp.zeros(k, bool).at[slot].set(flat_ok, mode="drop")
    return halo_x, halo_ok


def prev_last_value(x, valid, count, axis: str):
    """The last real row's (value, value_ok, exists) from the nearest
    non-empty predecessor shard, in the ORIGINAL dtype (no float64
    round-trip — int64 ticks stay exact). Used for cross-shard tie
    detection in global ranking."""
    cap = x.shape[0]
    last_i = jnp.clip(count - 1, 0, cap - 1)
    lv = x[last_i]
    padmask = K.row_mask(count, cap)
    lok = _ok(x, valid, padmask)[last_i] & (count > 0)
    have = count > 0
    all_v = lax.all_gather(lv, axis)         # [S]
    all_ok = lax.all_gather(lok, axis)
    all_have = lax.all_gather(have, axis)
    S = all_v.shape[0]
    r = lax.axis_index(axis)
    ids = jnp.arange(S)
    cand = all_have & (ids < r)
    best = jnp.max(jnp.where(cand, ids, -1))
    exists = best >= 0
    sel = jnp.clip(best, 0, S - 1)
    return all_v[sel], all_ok[sel] & exists, exists


# ---------------------------------------------------------------------------
# shift / diff
# ---------------------------------------------------------------------------

def shift_local(x, valid, count, halo_x, halo_ok, n: int):
    """Shift by n>0 (from previous rows; halo has the last n rows of the
    previous shard). Returns (data, ok)."""
    cap = x.shape[0]
    padmask = K.row_mask(count, cap)
    ok = _ok(x, valid, padmask)
    ext = jnp.concatenate([halo_x, x.astype(jnp.float64)])
    ext_ok = jnp.concatenate([halo_ok, ok])
    out = ext[:cap]
    out_ok = ext_ok[:cap] & padmask
    return jnp.where(out_ok, out, jnp.nan), out_ok


# ---------------------------------------------------------------------------
# partitioned ranking windows: ROW_NUMBER / RANK / DENSE_RANK / NTILE /
# CUMCOUNT over (PARTITION BY keys ORDER BY order_cols)
# ---------------------------------------------------------------------------

@bounded_jit(static_argnames=("specs", "num_keys", "ascending",
                                   "na_last"))
def rank_window_local(key_arrays, order_arrays, count,
                      specs: Tuple[Tuple[str, int], ...], num_keys: int,
                      ascending: Tuple[bool, ...] = (),
                      na_last: bool = True):
    """Ranking window functions in one sorted pass.

    TPU-native replacement for the reference's window-function family
    (bodo/libs/window/_window_aggfuncs.cpp, _window_calculator.cpp):
    stable sort by (partition keys, order cols), segment boundaries from
    key changes, then each rank flavor is an elementwise/scan expression
    over segment-relative positions; results scatter back to the input
    row order. specs: (op, param) with op in row_number/rank/dense_rank/
    ntile/cumcount; param is ntile's bucket count.

    Null partition keys form their own partition (SQL semantics: NULLs
    group together in PARTITION BY). Returns int64 outputs aligned with
    input rows (0 on padding rows).
    """
    cap = key_arrays[0][0].shape[0] if key_arrays else \
        order_arrays[0][0].shape[0]
    (perm, padmask_s, seg, seg_start, seg_end, seg_cnt_row, newval,
     peer_end, pos) = _sorted_segments(key_arrays, order_arrays, count,
                                       ascending, na_last, cap)
    n_segs = cap
    row_no = pos - seg_start + 1                          # 1-based
    dense = jnp.cumsum(newval & padmask_s)
    dense_rank = dense - jax.ops.segment_min(
        jnp.where(padmask_s, dense, cap + 1), seg, num_segments=n_segs
    )[seg] + 1
    # rank: row_number of the first row with an equal order value
    first_eq = jnp.where(newval, pos, 0)
    first_eq = jax.lax.cummax(first_eq)                   # last change point
    rank = first_eq - seg_start + 1

    outs_sorted = []
    for op, param in specs:
        if op == "row_number":
            o = row_no
        elif op == "cumcount":
            o = row_no - 1
        elif op == "rank":
            o = rank
        elif op == "dense_rank":
            o = dense_rank
        elif op == "ntile":
            # SQL NTILE: first (cnt mod n) buckets get ceil(cnt/n) rows,
            # the rest floor(cnt/n) (ref _window_aggfuncs.cpp ntile)
            if int(param) < 1:
                raise ValueError(
                    f"NTILE argument must be positive, got {param}")
            n = jnp.int64(param)
            cnt = jnp.maximum(seg_cnt_row, 1)
            small = cnt // n
            rem = cnt - small * n
            big_rows = rem * (small + 1)       # rows in the big buckets
            r0 = row_no - 1
            o = jnp.where(
                r0 < big_rows,
                r0 // (small + 1) + 1,
                rem + (r0 - big_rows) // jnp.maximum(small, 1) + 1)
        else:
            raise ValueError(f"unknown rank window op: {op}")
        outs_sorted.append(jnp.where(padmask_s, o, 0).astype(jnp.int64))

    # scatter back to input row order
    inv = jnp.zeros(cap, dtype=jnp.int64).at[perm].set(pos)
    return tuple(o[inv] for o in outs_sorted)


# ---------------------------------------------------------------------------
# partitioned aggregate windows: SUM/AVG/MIN/MAX/COUNT ... OVER
# (PARTITION BY k ORDER BY o [ROWS BETWEEN a AND b]) + LEAD/LAG +
# FIRST_VALUE/LAST_VALUE
# ---------------------------------------------------------------------------

def _sorted_segments(key_arrays, order_arrays, count, ascending, na_last,
                     cap: int):
    """Shared sort/segment machinery for ALL partitioned window kernels:
    stable sort by (partition keys, order cols); partition boundaries
    from null-canonicalized key changes (a null — mask or NaN — compares
    equal to another null, never to a value; raw NaN != NaN would split
    every null row into its own group). Returns per-row arrays in sorted
    order: (perm, padmask_s, seg, seg_start, seg_end, seg_cnt_row,
    newval, peer_end, pos)."""
    from bodo_tpu.ops import kernels as K
    from bodo_tpu.ops import sort_encoding as SE

    padmask = K.row_mask(count, cap)
    operands: list = []
    for d, v in key_arrays:
        # partition nulls group together: use the null rank slot but keep
        # them, padding rows still sort last
        operands.extend(SE.key_operands(d, v, padmask=padmask))
    if not ascending:
        ascending = tuple(True for _ in order_arrays)
    for (d, v), asc in zip(order_arrays, ascending):
        operands.extend(SE.key_operands(d, v, ascending=asc,
                                        na_last=na_last, padmask=padmask))
    perm = SE.stable_argsort(operands) if operands else jnp.arange(cap)
    padmask_s = padmask[perm]
    pos = jnp.arange(cap)

    def _changes(arrays):
        chg = jnp.zeros(cap, dtype=bool)
        for d, v in arrays:
            null = SE.null_flag(d, v)
            ds = d[perm]
            if null is not None:
                ns = null[perm]
                ds = jnp.where(ns, jnp.zeros((), d.dtype), ds)
                chg = chg | (ns != jnp.roll(ns, 1))
            chg = chg | (ds != jnp.roll(ds, 1))
        return chg

    newpart = (_changes(key_arrays) & padmask_s) | (pos == 0)
    seg = jnp.maximum(jnp.cumsum(newpart) - 1, 0)
    n_segs = cap
    seg_start = jax.ops.segment_min(jnp.where(padmask_s, pos, cap), seg,
                                    num_segments=n_segs)[seg]
    seg_cnt_row = jax.ops.segment_sum(padmask_s.astype(jnp.int64), seg,
                                      num_segments=n_segs)[seg]
    seg_end = seg_start + seg_cnt_row - 1
    # peer groups: rows equal on ALL order keys (RANGE frame boundary)
    newval = newpart | (_changes(order_arrays) & padmask_s)
    peer = jnp.cumsum(newval & padmask_s)
    peer_end = jax.ops.segment_max(jnp.where(padmask_s, pos, -1), peer,
                                   num_segments=cap + 1)[peer]
    return (perm, padmask_s, seg, seg_start, seg_end, seg_cnt_row,
            newval, peer_end, pos)


def _minmax_sparse_table(x_masked, n_levels: int, want_max: bool):
    """Sparse-table levels for range-min/max queries: levels[k][i] =
    red(x[i .. i+2^k-1]) (array-clamped; queries stay inside segments so
    no segment masking is needed at build time). Works in the value's own
    domain dtype (int64 for integers/datetimes/decimals, float for
    floats) so results are EXACT — no float64 round-trip."""
    red = jnp.maximum if want_max else jnp.minimum
    cap = x_masked.shape[0]
    levels = [x_masked]
    span = 1
    for _ in range(n_levels - 1):
        prev = levels[-1]
        idx = jnp.minimum(jnp.arange(cap) + span, cap - 1)
        levels.append(red(prev, prev[idx]))
        span *= 2
    return jnp.stack(levels)  # [K, cap]


def _range_minmax(levels, a, b, empty, want_max: bool, sentinel):
    """min/max over [a, b] per row from sparse-table levels ([K, cap]).

    floor(log2(length)) is computed by a static unrolled compare chain
    over the (few) levels — no frexp/bitcast, which the TPU x64-rewrite
    pass rejects."""
    length = jnp.maximum(b - a + 1, 1)
    n_levels = levels.shape[0]
    k = jnp.zeros(length.shape, dtype=jnp.int32)
    for j in range(1, n_levels):
        k = jnp.where(length >= (1 << j), j, k)
    cap = levels.shape[1]
    left = levels[k, jnp.clip(a, 0, cap - 1)]
    right = levels[k, jnp.clip(b - (1 << jnp.clip(k, 0, 62)) + 1,
                               0, cap - 1)]
    red = jnp.maximum if want_max else jnp.minimum
    out = red(left, right)
    return jnp.where(empty, sentinel, out)


@bounded_jit(static_argnames=("specs", "num_keys", "ascending",
                                   "na_last"))
def agg_window_local(key_arrays, order_arrays, val_arrays, count,
                     specs: Tuple, num_keys: int,
                     ascending: Tuple[bool, ...] = (),
                     na_last: bool = True):
    """Aggregate/navigation window functions in one sorted pass.

    TPU-native replacement for the reference's window aggregate family
    (bodo/libs/window/_window_aggfuncs.cpp WindowAggfunc,
    bodo/libs/_lead_lag.cpp): sort once by (partition, order) keys, then
    every frame aggregate is a prefix-sum difference (sum/count/mean) or
    a sparse-table range query (min/max) over the sorted array —
    O(n log n) total, no per-row loops, MXU/VPU-friendly static shapes.

    specs: tuple of (op, val_idx, frame, param):
      op    ∈ sum/mean/count/min/max/lead/lag/first_value/last_value
      frame ∈ ("all",)                — whole partition (no ORDER BY)
              ("cumrange",)           — RANGE UNBOUNDED PRECEDING..CURRENT
                                        ROW (ORDER BY default; peers incl.)
              ("rows", lo, hi)        — ROWS BETWEEN frames; lo/hi are
                                        row offsets (None = unbounded)
      param — LEAD/LAG offset (ignored otherwise)

    Returns one (data, valid_bool) pair per spec, aligned with input
    rows: prefix-sum ops (sum/mean/count) in float64; min/max in the
    value's exact domain (int64 for ints/datetimes/decimals, float64 for
    floats); gather ops (lead/lag/first/last) in the SOURCE dtype so
    dictionary codes and datetimes survive."""
    from bodo_tpu.ops import kernels as K

    cap = (key_arrays[0][0].shape[0] if key_arrays
           else (order_arrays[0][0].shape[0] if order_arrays
                 else val_arrays[0][0].shape[0]))
    (perm, padmask_s, seg, seg_start, seg_end, _seg_cnt, _newval,
     peer_end, pos) = _sorted_segments(key_arrays, order_arrays, count,
                                       ascending, na_last, cap)
    padmask = K.row_mask(count, cap)

    # per-value-column sorted data, ok masks, prefix sums (built lazily)
    sorted_cache: dict = {}

    def _sorted_val(vi):
        if vi not in sorted_cache:
            d, v = val_arrays[vi]
            ok = K.value_ok(d, v, padmask)
            sorted_cache[vi] = (d[perm], ok[perm])
        return sorted_cache[vi]

    prefix_cache: dict = {}

    def _prefixes(vi):
        if vi not in prefix_cache:
            ds, oks = _sorted_val(vi)
            xf = jnp.where(oks, ds.astype(jnp.float64), 0.0)
            P0 = jnp.concatenate([jnp.zeros(1), jnp.cumsum(xf)])
            C0 = jnp.concatenate([jnp.zeros(1, jnp.int64),
                                  jnp.cumsum(oks.astype(jnp.int64))])
            prefix_cache[vi] = (P0, C0)
        return prefix_cache[vi]

    n_levels = max(int(np.ceil(np.log2(max(cap, 2)))) + 1, 1)
    table_cache: dict = {}

    def _tables(vi, want_max: bool):
        """Sparse table + sentinel in the value's exact domain: floats
        stay float (widened to f64), everything else (ints, bools,
        datetime ticks, decimal scaled-ints) runs in int64 so min/max
        round-trip exactly (large ids, timestamps, 18-digit decimals)."""
        key = (vi, want_max)
        if key not in table_cache:
            ds, oks = _sorted_val(vi)
            if jnp.issubdtype(ds.dtype, jnp.floating):
                dom = ds.astype(jnp.float64)
                sentinel = -jnp.inf if want_max else jnp.inf
            elif ds.dtype == jnp.uint64:
                # int64 would wrap values >= 2^63 negative — stay unsigned
                dom = ds
                ii = jnp.iinfo(jnp.uint64)
                sentinel = jnp.asarray(ii.min if want_max else ii.max,
                                       dtype=jnp.uint64)
            else:
                dom = ds.astype(jnp.int64)
                ii = jnp.iinfo(jnp.int64)
                sentinel = jnp.asarray(ii.min if want_max else ii.max,
                                       dtype=jnp.int64)
            xm = jnp.where(oks, dom, sentinel)
            table_cache[key] = (
                _minmax_sparse_table(xm, n_levels, want_max), sentinel)
        return table_cache[key]

    def _frame_bounds(frame):
        if frame[0] == "all":
            return seg_start, seg_end
        if frame[0] == "cumrange":
            return seg_start, peer_end
        lo, hi = frame[1], frame[2]
        a = seg_start if lo is None else jnp.maximum(pos + lo, seg_start)
        b = seg_end if hi is None else jnp.minimum(pos + hi, seg_end)
        return a, b

    outs = []
    inv = jnp.zeros(cap, dtype=jnp.int64).at[perm].set(pos)
    for op, vi, frame, param in specs:
        if op in ("lead", "lag"):
            off = int(param) * (1 if op == "lead" else -1)
            tgt = pos + off
            ds, oks = _sorted_val(vi)
            inside = (tgt >= seg_start) & (tgt <= seg_end) & padmask_s
            safe = jnp.clip(tgt, 0, cap - 1)
            od = jnp.where(inside, ds[safe], jnp.zeros((), ds.dtype))
            ov = inside & oks[safe]
        elif op in ("first_value", "last_value"):
            a, b = _frame_bounds(frame)
            ds, oks = _sorted_val(vi)
            at = a if op == "first_value" else b
            nonempty = (b >= a) & padmask_s
            safe = jnp.clip(at, 0, cap - 1)
            od = jnp.where(nonempty, ds[safe], jnp.zeros((), ds.dtype))
            ov = nonempty & oks[safe]
        elif op in ("sum", "sum0", "mean", "count"):
            a, b = _frame_bounds(frame)
            P0, C0 = _prefixes(vi)
            a_ = jnp.clip(a, 0, cap)
            b_ = jnp.clip(b + 1, 0, cap)
            nonempty = (b >= a) & padmask_s
            wsum = jnp.where(nonempty, P0[b_] - P0[a_], 0.0)
            wcnt = jnp.where(nonempty, C0[b_] - C0[a_], 0)
            if op == "count":
                od = wcnt.astype(jnp.float64)
                ov = padmask_s
            elif op == "sum":
                od = wsum
                ov = wcnt > 0          # SQL: SUM over empty/all-null=NULL
            elif op == "sum0":
                od = wsum              # pandas: empty/all-null sums to 0
                ov = padmask_s
            else:
                od = wsum / jnp.maximum(wcnt, 1)
                ov = wcnt > 0
        elif op in ("min", "max"):
            a, b = _frame_bounds(frame)
            lv, sentinel = _tables(vi, op == "max")
            _, C0 = _prefixes(vi)
            empty = (b < a) | ~padmask_s
            m = _range_minmax(lv, a, b, empty, op == "max", sentinel)
            # validity from the non-null COUNT, not isfinite(m): a real
            # +/-inf data value must survive as inf, not become NULL
            wcnt = jnp.where(empty, 0,
                             C0[jnp.clip(b + 1, 0, cap)]
                             - C0[jnp.clip(a, 0, cap)])
            ov = wcnt > 0
            od = jnp.where(ov, m, jnp.zeros((), m.dtype))
        else:
            raise ValueError(f"unknown agg window op: {op}")
        # scatter back to input row order
        outs.append((od[inv], ov[inv]))
    return tuple(outs)
