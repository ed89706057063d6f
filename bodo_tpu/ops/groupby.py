"""Groupby aggregation kernels.

TPU-native replacement for the reference's hash-groupby C++ family
(bodo/libs/groupby/_groupby*.cpp, streaming/_groupby.cpp). Instead of
hash tables we use the XLA-friendly sort+segment-reduce recipe
(SURVEY.md §7): stable multi-key sort on encoded keys, segment ids from
group boundaries, `jax.ops.segment_*` reductions onto the MXU/VPU.

Aggregations are split into decomposable partial ops + combine + finalize
(the reference's decomposition strategy for its distributed combine step,
bodo/libs/groupby/_groupby_update.cpp), which powers the two-phase
distributed groupby: local pre-aggregation → hash-partition all_to_all
shuffle → combine (parallel/shuffle.py).

var/std use the numerically stable (count, sum, m2) moments with
m2 = Σ(x − mean)² accumulated in float64 (two-pass locally; the
cross-shard term is recovered from per-shard sums at combine — the same
stable var_combine the reference implements,
bodo/libs/groupby/_groupby_update.cpp), never the catastrophically
cancelling E[x²] − E[x]² form.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bodo_tpu.ops import kernels as K
from bodo_tpu.ops import sort_encoding as SE
from bodo_tpu.utils.kernel_cache import bounded_jit

# ---------------------------------------------------------------------------
# agg spec plumbing
# ---------------------------------------------------------------------------

# final op -> (partial ops, combine ops on partial cols)
# var/std partials: float64 (count, sum, m2); the combine for m2 is the
# composite "chan_m2" (exact delta-form Chan combine) which reads the two
# preceding columns (count, sum) — the triple MUST stay in this order.
_VAR_PARTS = ["count", "sum64", "m2"]
# skew/kurt partials extend the stable-moments triple with the centered
# third/fourth moments; their combines are the exact delta-form Chan
# transforms (see chan_m3/chan_m4 in _groupby_local_impl) which read the
# preceding columns — the order here is load-bearing.
_SKEW_PARTS = ["count", "sum64", "m2", "m3"]
_KURT_PARTS = ["count", "sum64", "m2", "m3", "m4"]
DECOMPOSE: Dict[str, List[str]] = {
    "sum": ["sum"],
    "sumnull": ["sumnull"],
    "prod": ["prod"],
    "count": ["count"],
    "size": ["size"],
    "min": ["min"],
    "max": ["max"],
    "first": ["first"],
    "last": ["last"],
    "mean": ["sum", "count"],
    "var": _VAR_PARTS,
    "std": _VAR_PARTS,
    "var0": _VAR_PARTS,
    "std0": _VAR_PARTS,
    "skew": _SKEW_PARTS,
    "kurt": _KURT_PARTS,
}
COMBINE_OF = {"sum": "sum", "sumnull": "sumnull", "sum64": "sum",
              "m2": "chan_m2", "m3": "chan_m3", "m4": "chan_m4",
              "count": "sum", "size": "sum",
              "min": "min", "max": "max", "first": "first", "last": "last",
              "prod": "prod"}


def agg_dtype(op: str, src) -> "object":
    """Logical result DType of an aggregation (decimal-aware): the single
    source of truth shared by plan schema inference and the executors."""
    from bodo_tpu.table import dtypes as dt
    if op in ("count", "size", "nunique"):
        return dt.INT64
    if op.startswith(("listagg", "listaggd")):
        return dt.STRING
    if op in ("min", "max", "first", "last", "mode"):
        return src
    if dt.is_decimal(src):
        if op == "prod":
            raise NotImplementedError(
                "prod over a decimal column: the product of n values "
                "carries scale n·s, which a fixed-scale column can't hold")
        if op in ("sum", "sumnull"):
            # sums overflow the source precision; widen to the full 18
            # digits an int64 holds (scale preserved, values exact)
            return dt.decimal(src.scale)
        return dt.FLOAT64  # mean/var/std/quantiles descale to float
    return dt.from_numpy(result_dtype(op, src.numpy))


def agg_descale_factor(op: str, src) -> float:
    """Factor dividing a physical agg output of a decimal column to get
    the logical float value (1.0 when no descale applies)."""
    from bodo_tpu.table import dtypes as dt
    if not dt.is_decimal(src):
        return 1.0
    if op in ("sum", "sumnull", "prod", "min", "max", "first", "last",
              "count", "size", "nunique", "skew", "kurt", "mode"):
        # skew/kurt are standardized (scale cancels); mode keeps the dtype
        return 1.0
    if op in ("var", "var0"):
        return 10.0 ** (2 * src.scale)
    return 10.0 ** src.scale  # mean/std/median/quantiles


def result_dtype(op: str, dtype):
    d = jnp.dtype(dtype)
    if op in ("count", "size", "nunique"):
        return jnp.dtype(jnp.int64)
    if op in ("sum64", "m2", "m3", "m4", "skew", "kurt"):
        return jnp.dtype(jnp.float64)  # stable moments always accumulate f64
    if op in ("mean", "var", "std", "var0", "std0", "median") or \
            op.startswith(("quantile_", "q:")):
        return jnp.dtype(jnp.float32) if d == jnp.float32 else jnp.dtype(jnp.float64)
    if op in ("sum", "sumnull", "prod"):
        if jnp.issubdtype(d, jnp.floating):
            return d
        if jnp.issubdtype(d, jnp.unsignedinteger):
            return jnp.dtype(jnp.uint64)
        return jnp.dtype(jnp.int64)
    return d  # min/max/first/last


# ---------------------------------------------------------------------------
# core local kernel
# ---------------------------------------------------------------------------

def _group_segments(keys: Sequence[Tuple], count, row_valid=None):
    """Sort rows by keys; return (perm, seg_ids, new_group, padmask_s,
    n_groups). Null-keyed rows are excluded (pandas dropna=True).

    row_valid (optional bool[cap]) marks live rows directly instead of the
    first-`count`-rows convention — used by the streaming merge where live
    rows sit in two packed blocks (state ∪ batch partials)."""
    cap = keys[0][0].shape[0]
    padmask = K.row_mask(count, cap) if row_valid is None else row_valid
    for data, valid in keys:
        if valid is not None:
            padmask = padmask & valid
        if jnp.issubdtype(data.dtype, jnp.floating):
            padmask = padmask & ~jnp.isnan(data)

    operands: list = []
    for d, v in keys:
        operands.extend(SE.key_operands(d, v, padmask=padmask))
    perm = SE.stable_argsort(operands)
    padmask_s = padmask[perm]

    pos = jnp.arange(cap)
    diff = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for data, _ in keys:
        ks = data[perm]
        diff = diff | (ks != jnp.roll(ks, 1))
    new_group = padmask_s & (diff | (pos == 0))
    seg = jnp.maximum(jnp.cumsum(new_group) - 1, 0)
    n_groups = jnp.sum(new_group)
    return perm, seg, new_group, padmask_s, n_groups


def _segment_agg(op: str, v_s, valid_s, seg, padmask_s, out_cap: int):
    """One primitive aggregation over sorted values. Returns (data, valid)."""
    ok = K.value_ok(v_s, valid_s, padmask_s)
    cnt = jax.ops.segment_sum(ok.astype(jnp.int64), seg, num_segments=out_cap)
    rdt = result_dtype(op, v_s.dtype)

    if op == "count":
        return cnt, None
    if op == "size":
        sz = jax.ops.segment_sum(padmask_s.astype(jnp.int64), seg,
                                 num_segments=out_cap)
        return sz, None
    if op in ("sum", "sumnull", "sum64"):
        v = v_s.astype(rdt)
        s = jax.ops.segment_sum(jnp.where(ok, v, 0), seg, num_segments=out_cap)
        if op == "sumnull":  # SQL: SUM over all-null group is NULL
            return s, cnt > 0
        return s, None  # pandas: sum over all-null = 0
    if op == "m2":
        # stable centered second moment Σ(x − mean)², always float64
        v = v_s.astype(jnp.float64)
        s = jax.ops.segment_sum(jnp.where(ok, v, 0.0), seg,
                                num_segments=out_cap)
        mean = s / jnp.maximum(cnt, 1).astype(jnp.float64)
        d = jnp.where(ok, v - mean[seg], 0.0)
        return jax.ops.segment_sum(d * d, seg, num_segments=out_cap), None
    if op == "prod":
        v = v_s.astype(rdt)
        p = jax.ops.segment_prod(jnp.where(ok, v, 1), seg, num_segments=out_cap)
        return p, None
    if op in ("min", "max"):
        if jnp.issubdtype(v_s.dtype, jnp.floating):
            ident = jnp.array(np.inf if op == "min" else -np.inf, v_s.dtype)
        elif v_s.dtype == jnp.bool_:
            ident = jnp.array(op == "min", jnp.bool_)
        else:
            info = jnp.iinfo(v_s.dtype)
            ident = jnp.array(info.max if op == "min" else info.min, v_s.dtype)
        v = jnp.where(ok, v_s, ident)
        f = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        out = f(v, seg, num_segments=out_cap)
        return out, cnt > 0
    if op in ("first", "last"):
        cap = v_s.shape[0]
        if op == "first":
            idx_enc = jnp.where(ok, jnp.arange(cap), cap)
            idx = jax.ops.segment_min(idx_enc, seg, num_segments=out_cap)
        else:
            idx_enc = jnp.where(ok, jnp.arange(cap), -1)
            idx = jax.ops.segment_max(idx_enc, seg, num_segments=out_cap)
        has = (idx >= 0) & (idx < cap)
        out = v_s[jnp.clip(idx, 0, cap - 1)]
        out = jnp.where(has, out, 0)
        return out, has
    if op == "mean":
        v = v_s.astype(rdt)
        s = jax.ops.segment_sum(jnp.where(ok, v, 0), seg, num_segments=out_cap)
        m = s / jnp.maximum(cnt, 1)
        return jnp.where(cnt > 0, m, jnp.nan), None
    if op in ("var", "std", "var0", "std0"):
        # two-pass: mean, then Σ(x − mean)², accumulated in float64
        v = v_s.astype(jnp.float64)
        s = jax.ops.segment_sum(jnp.where(ok, v, 0.0), seg,
                                num_segments=out_cap)
        mean = s / jnp.maximum(cnt, 1).astype(jnp.float64)
        d = jnp.where(ok, v - mean[seg], 0.0)
        m2 = jax.ops.segment_sum(d * d, seg, num_segments=out_cap)
        out = _var_from_m2(m2, cnt, ddof=0 if op.endswith("0") else 1)
        if op.startswith("std"):
            out = jnp.sqrt(out)
        return out.astype(rdt), None
    if op in ("m3", "m4", "skew", "kurt"):
        # centered higher moments, two-pass like m2 (reference:
        # bodo/libs/groupby/ skew/kurt ftypes)
        v = v_s.astype(jnp.float64)
        s = jax.ops.segment_sum(jnp.where(ok, v, 0.0), seg,
                                num_segments=out_cap)
        mean = s / jnp.maximum(cnt, 1).astype(jnp.float64)
        d = jnp.where(ok, v - mean[seg], 0.0)
        m2 = jax.ops.segment_sum(d * d, seg, num_segments=out_cap)
        m3 = jax.ops.segment_sum(d * d * d, seg, num_segments=out_cap)
        if op == "m3":
            return m3, None
        if op == "skew":
            return _skew_from_moments(cnt, m2, m3), None
        m4 = jax.ops.segment_sum(d * d * d * d, seg,
                                 num_segments=out_cap)
        if op == "m4":
            return m4, None
        return _kurt_from_moments(cnt, m2, m4), None
    if op == "nunique":
        raise NotImplementedError("nunique handled in groupby_local")
    raise ValueError(f"unknown agg op: {op}")


def _var_from_m2(m2, cnt, ddof: int = 1):
    """Variance from the centered second moment M2 = Σ(x − mean)²."""
    cntf = cnt.astype(m2.dtype)
    var = m2 / jnp.maximum(cntf - ddof, 1)
    return jnp.where(cnt > ddof, jnp.maximum(var, 0), jnp.nan)


def _skew_from_moments(cnt, m2, m3):
    """pandas-adjusted (Fisher-Pearson) skew from centered moments:
    g1·sqrt(n(n−1))/(n−2) with g1 = (M3/n)/(M2/n)^1.5. Matches pandas
    nanskew: NaN for n<3; 0.0 for zero-variance (constant) groups."""
    n = cnt.astype(jnp.float64)
    safe_m2 = jnp.maximum(m2, 1e-300)
    g1 = (m3 / jnp.maximum(n, 1)) / (safe_m2 / jnp.maximum(n, 1)) ** 1.5
    adj = jnp.sqrt(n * (n - 1)) / jnp.maximum(n - 2, 1)
    out = g1 * adj
    # pandas nanskew: constant groups (m2 == 0) are 0, not NaN
    out = jnp.where(m2 > 0, out, 0.0)
    return jnp.where(cnt >= 3, out, jnp.nan)


def _kurt_from_moments(cnt, m2, m4):
    """pandas-adjusted (Fisher, excess) kurtosis from centered moments:
    [n(n+1)(n−1)·M4/((n−2)(n−3)·M2²)] − 3(n−1)²/((n−2)(n−3)); NaN for
    n<4 or zero variance."""
    n = cnt.astype(jnp.float64)
    safe_m2 = jnp.maximum(m2, 1e-300)
    den = jnp.maximum((n - 2) * (n - 3), 1)
    out = n * (n + 1) * (n - 1) * m4 / (den * safe_m2 * safe_m2) \
        - 3.0 * (n - 1) * (n - 1) / den
    # pandas nankurt: constant groups (m2 == 0) are 0, not NaN
    out = jnp.where(m2 > 0, out, 0.0)
    return jnp.where(cnt >= 4, out, jnp.nan)


def _groupby_local_impl(arrays, count, specs: Tuple[str, ...],
                        out_capacity: int, num_keys: int, row_valid=None):
    keys = arrays[:num_keys]
    values = arrays[num_keys:]
    perm, seg, new_group, padmask_s, n_groups = _group_segments(
        keys, count, row_valid)

    out_keys = []
    idx_scatter = jnp.where(new_group, seg, out_capacity)
    for data, valid in keys:
        k_s = data[perm]
        z = jnp.zeros((out_capacity,), dtype=data.dtype)
        out_keys.append((z.at[idx_scatter].set(k_s, mode="drop"), None))

    out_vals = []
    for i, ((data, valid), op) in enumerate(zip(values, specs)):
        v_s = data[perm]
        valid_s = valid[perm] if valid is not None else None
        if op == "nunique":
            out_vals.append(_nunique(keys, (data, valid), perm, seg,
                                     padmask_s, out_capacity))
        elif op == "mode":
            out_vals.append(_mode((data, valid), perm, seg, padmask_s,
                                  out_capacity))
        elif op.startswith("q:"):  # quantile/median: "q:<float>"
            out_vals.append(_quantile_seg((data, valid), perm, seg,
                                          padmask_s, out_capacity,
                                          float(op[2:])))
        elif op == "chan_m2":
            # composite combine of per-shard (n, sum, m2) partial rows:
            # M2 = Σm2ᵢ + Σnᵢ·(meanᵢ − mean)² — the exact delta-form Chan
            # combine (reference bodo/libs/groupby/_groupby_update.cpp
            # var_combine). Reads the two preceding value columns, which
            # _VAR_PARTS pins to (count, sum64).
            n_s = values[i - 2][0][perm].astype(jnp.float64)
            s_s = values[i - 1][0][perm].astype(jnp.float64)
            m2_s = v_s.astype(jnp.float64)
            okr = K.value_ok(m2_s, valid_s, padmask_s)
            n_tot = jax.ops.segment_sum(jnp.where(okr, n_s, 0.0), seg,
                                        num_segments=out_capacity)
            s_tot = jax.ops.segment_sum(jnp.where(okr, s_s, 0.0), seg,
                                        num_segments=out_capacity)
            mean = s_tot / jnp.maximum(n_tot, 1.0)
            delta = s_s / jnp.maximum(n_s, 1.0) - mean[seg]
            cross = jax.ops.segment_sum(
                jnp.where(okr, n_s * delta * delta, 0.0), seg,
                num_segments=out_capacity)
            m2 = jax.ops.segment_sum(jnp.where(okr, m2_s, 0.0), seg,
                                     num_segments=out_capacity)
            out_vals.append((m2 + cross, None))
        elif op in ("chan_m3", "chan_m4"):
            # exact delta-form combine of centered higher moments: with
            # d_i = mean_i − mean,
            #   M3 = Σ m3_i + 3 d_i m2_i + n_i d_i³
            #   M4 = Σ m4_i + 4 d_i m3_i + 6 d_i² m2_i + n_i d_i⁴
            # reads the preceding partial columns pinned by
            # _SKEW_PARTS/_KURT_PARTS order (count, sum64, m2[, m3]).
            back = 3 if op == "chan_m3" else 4
            n_s = values[i - back][0][perm].astype(jnp.float64)
            s_s = values[i - back + 1][0][perm].astype(jnp.float64)
            m2_s = values[i - back + 2][0][perm].astype(jnp.float64)
            m3_s = (values[i - 1][0][perm].astype(jnp.float64)
                    if op == "chan_m4" else v_s.astype(jnp.float64))
            mk_s = v_s.astype(jnp.float64)
            okr = K.value_ok(mk_s, valid_s, padmask_s)
            n_tot = jax.ops.segment_sum(jnp.where(okr, n_s, 0.0), seg,
                                        num_segments=out_capacity)
            s_tot = jax.ops.segment_sum(jnp.where(okr, s_s, 0.0), seg,
                                        num_segments=out_capacity)
            mean = s_tot / jnp.maximum(n_tot, 1.0)
            d = s_s / jnp.maximum(n_s, 1.0) - mean[seg]
            if op == "chan_m3":
                term = mk_s + 3.0 * d * m2_s + n_s * d * d * d
            else:
                term = mk_s + 4.0 * d * m3_s + 6.0 * d * d * m2_s \
                    + n_s * d * d * d * d
            out_vals.append((jax.ops.segment_sum(
                jnp.where(okr, term, 0.0), seg,
                num_segments=out_capacity), None))
        else:
            out_vals.append(_segment_agg(op, v_s, valid_s, seg, padmask_s,
                                         out_capacity))
    return tuple(out_keys), tuple(out_vals), n_groups


@bounded_jit(static_argnames=("specs", "out_capacity", "num_keys"))
def groupby_local(arrays, count, specs: Tuple[str, ...], out_capacity: int,
                  num_keys: int):
    """Local (single-shard) groupby.

    arrays: tuple of (data, valid) — first `num_keys` are key columns, the
    rest align 1:1 with `specs` (one value column per agg op; repeat the
    column for multiple aggs on it).
    Returns (out_keys, out_vals, n_groups); outputs sorted by key ascending
    (pandas groupby sort=True), packed at the front of the capacity.
    """
    return _groupby_local_impl(arrays, count, specs, out_capacity, num_keys)


@bounded_jit(static_argnames=("specs", "out_capacity", "num_keys"))
def groupby_merge(state_arrays, batch_arrays, n_state, n_batch,
                  specs: Tuple[str, ...], out_capacity: int, num_keys: int):
    """Merge two packed partial-aggregate blocks (streaming accumulate).

    Both inputs are groupby outputs (live rows packed at the front):
    `state_arrays` holds the running partial state (n_state groups),
    `batch_arrays` the latest batch's partials (n_batch groups). Columns
    are concatenated and re-grouped under `specs` (the combine ops), so
    the result is again a packed partial block. This is the streaming
    groupby's accumulate step (reference analogue: the streaming groupby
    build state update, bodo/libs/streaming/_groupby.cpp
    GroupbyState::UpdateGroupsAndCombine)."""
    state_cap = state_arrays[0][0].shape[0]
    batch_cap = batch_arrays[0][0].shape[0]
    mask = jnp.concatenate([jnp.arange(state_cap) < n_state,
                            jnp.arange(batch_cap) < n_batch])

    def cat(sv, bv):
        s_d, s_v = sv
        b_d, b_v = bv
        d = jnp.concatenate([s_d, b_d.astype(s_d.dtype)])
        if s_v is None and b_v is None:
            v = None
        else:
            ones_s = jnp.ones(state_cap, bool) if s_v is None else s_v
            ones_b = jnp.ones(batch_cap, bool) if b_v is None else b_v
            v = jnp.concatenate([ones_s, ones_b])
        return (d, v)

    merged = tuple(cat(s, b) for s, b in zip(state_arrays, batch_arrays))
    return _groupby_local_impl(merged, None, specs, out_capacity, num_keys,
                               row_valid=mask)


def _quantile_seg(value, perm, seg, padmask_s, out_cap: int, q: float):
    """Per-group linear-interpolated quantile (pandas interpolation=
    'linear'; reference analogue bodo/libs/_quantile_alg.cpp): re-sort by
    (group, value) with the raw value as payload, then pick/interpolate
    at (cnt−1)·q per segment."""
    data, valid = value
    cap = data.shape[0]
    v_s = data[perm]
    valid_s = valid[perm] if valid is not None else None
    ok = K.value_ok(v_s, valid_s, padmask_s)
    enc_v = SE.encode_value(v_s)
    seg_key = jnp.where(ok, seg, cap).astype(jnp.int64)
    s_seg, _, s_val = lax.sort(
        (seg_key.view(jnp.uint64), enc_v, v_s.astype(jnp.float64)),
        num_keys=2, is_stable=False)
    pos = jnp.arange(cap)
    okrow = s_seg < jnp.uint64(cap)
    seg_i = jnp.minimum(s_seg, jnp.uint64(out_cap)).astype(jnp.int64)
    start = jax.ops.segment_min(jnp.where(okrow, pos, cap), seg_i,
                                num_segments=out_cap + 1)[:out_cap]
    cnt = jax.ops.segment_sum(okrow.astype(jnp.int64), seg_i,
                              num_segments=out_cap + 1)[:out_cap]
    qpos = (cnt - 1).astype(jnp.float64) * q
    lo = jnp.floor(qpos).astype(jnp.int64)
    hi = jnp.ceil(qpos).astype(jnp.int64)
    frac = qpos - lo.astype(jnp.float64)
    v_lo = s_val[jnp.clip(start + lo, 0, cap - 1)]
    v_hi = s_val[jnp.clip(start + hi, 0, cap - 1)]
    out = v_lo + (v_hi - v_lo) * frac
    return jnp.where(cnt > 0, out, jnp.nan), None


def _mode(value, perm, seg, padmask_s, out_cap: int):
    """Per-group mode (most frequent value; smallest on ties — the
    reference's deterministic mode, bodo/libs/groupby/ mode ftype):
    re-sort by (group, value), run-length the equal-value runs, then a
    two-stage argmax (max run length per group, then min value among
    max-length runs)."""
    data, valid = value
    cap = data.shape[0]
    v_s = data[perm]
    valid_s = valid[perm] if valid is not None else None
    ok = K.value_ok(v_s, valid_s, padmask_s)
    enc_v = SE.encode_value(v_s)
    seg_key = jnp.where(ok, seg, cap).astype(jnp.int64)
    s_seg, s_enc = lax.sort((seg_key.view(jnp.uint64), enc_v),
                            num_keys=2, is_stable=False)
    pos = jnp.arange(cap)
    okrow = s_seg < jnp.uint64(cap)
    newrun = (s_seg != jnp.roll(s_seg, 1)) | (s_enc != jnp.roll(s_enc, 1)) \
        | (pos == 0)
    run_id = jnp.cumsum(newrun) - 1
    run_len = jax.ops.segment_sum(okrow.astype(jnp.int64), run_id,
                                  num_segments=cap)
    this_len = run_len[run_id]
    seg_i = jnp.where(okrow, jnp.minimum(s_seg, jnp.uint64(out_cap))
                      .astype(jnp.int64), out_cap)
    best_len = jax.ops.segment_max(jnp.where(okrow, this_len, 0), seg_i,
                                   num_segments=out_cap + 1)[:out_cap]
    is_best = okrow & (this_len == best_len[jnp.clip(seg_i, 0, out_cap - 1)])
    big = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    best_enc = jax.ops.segment_min(jnp.where(is_best, s_enc, big), seg_i,
                                   num_segments=out_cap + 1)[:out_cap]
    cnt = jax.ops.segment_sum(okrow.astype(jnp.int64), seg_i,
                              num_segments=out_cap + 1)[:out_cap]
    has = cnt > 0
    # exact inverse of the order-preserving encoding — no f64 round-trip
    out = jnp.where(has, SE.decode_value(best_enc, data.dtype),
                    jnp.zeros((), data.dtype))
    return out, has


def _nunique(keys, value, perm, seg, padmask_s, out_cap: int):
    """nunique per group: re-sort by (group seg, value), count distinct
    adjacent values (reference analogue: groupby nunique path in
    bodo/libs/groupby/_groupby_ftypes.cpp)."""
    data, valid = value
    cap = data.shape[0]
    v_s = data[perm]
    valid_s = valid[perm] if valid is not None else None
    ok = K.value_ok(v_s, valid_s, padmask_s)
    # non-ok rows (nulls/padding) get seg_key = cap and sort last; among ok
    # rows the exact value encoding detects distinct adjacent values
    enc_v = SE.encode_value(v_s)
    seg_key = jnp.where(ok, seg, cap).astype(jnp.int64)
    s_seg, s_val = lax.sort((seg_key.view(jnp.uint64), enc_v), num_keys=2,
                            is_stable=False)
    pos = jnp.arange(cap)
    newv = (s_seg != jnp.roll(s_seg, 1)) | (s_val != jnp.roll(s_val, 1)) | (pos == 0)
    okrow = s_seg < jnp.uint64(cap)
    contrib = (newv & okrow).astype(jnp.int64)
    out = jax.ops.segment_sum(contrib,
                              jnp.minimum(s_seg, jnp.uint64(out_cap)).astype(jnp.int64),
                              num_segments=out_cap + 1)[:out_cap]
    return out, None


# ---------------------------------------------------------------------------
# hash-based local kernel (arbitrary key cardinality, no row sort)
# ---------------------------------------------------------------------------

# ops the hash path supports: everything _segment_agg computes from
# (seg, values) alone. Order-sensitive composites (nunique/mode/q:*) and
# the chan_* distributed combines stay on the sort path.
HASH_OPS = frozenset({
    "count", "size", "sum", "sumnull", "sum64", "prod", "min", "max",
    "first", "last", "mean", "var", "std", "var0", "std0",
    "m2", "m3", "m4", "skew", "kurt",
})


@bounded_jit
def _groupby_hashed_claim(key_arrays, count):
    """Claim dense group ids for arbitrary keys (no row sort)."""
    from bodo_tpu.ops import hashtable as HT

    cap = key_arrays[0][0].shape[0]
    padmask = K.row_mask(count, cap)
    codes, null_ok = HT.encode_columns(key_arrays, null_equal=False)
    ok = padmask if null_ok is None else (padmask & null_ok)
    T = HT.table_size(cap)
    slot, owner, _r, unresolved = HT.claim_slots(codes, ok, T)
    seg, group_row, n_groups = HT.densify(slot, owner, T)
    return seg, group_row, ok, n_groups, unresolved


@bounded_jit(static_argnames=("specs", "num_keys", "ng_cap"))
def _groupby_hashed_agg(arrays, seg, group_row, ok,
                        specs: Tuple[str, ...], num_keys: int, ng_cap: int):
    """Aggregate into the ng_cap-sized group space (hash order).

    The segment space is the (host-synced, rounded) GROUP count, not the
    row capacity — on TPU with few groups this is where the Pallas MXU
    one-hot accumulate takes over from scatter-adds."""
    from bodo_tpu.ops import pallas_kernels as PK

    keys = arrays[:num_keys]
    values = arrays[num_keys:]
    cap = keys[0][0].shape[0]
    seg = jnp.where(seg < ng_cap, seg, ng_cap)

    grs = jnp.minimum(jnp.maximum(group_row, 0), cap - 1)
    gvalid = (group_row >= 0)[:ng_cap]
    gkeys = tuple(data[grs][:ng_cap] for data, valid in keys)

    # MXU route: f32 sums/counts/means via one fused one-hot matmul
    # (a key-only group-by, a DISTINCT, has nothing to accumulate)
    mxu = ((PK.use_pallas() or PK.FORCE_INTERPRET) and bool(specs)
           and ng_cap <= PK.MAX_MATMUL_SLOTS and cap <= (1 << 24)
           and all(op in ("sum", "count", "size", "mean")
                   for op in specs)
           and all(op in ("count", "size") or
                   (jnp.issubdtype(d.dtype, jnp.floating)
                    and d.dtype.itemsize <= 4)
                   for (d, v), op in zip(values, specs)))
    if mxu:
        mcols, moks, plan = [], [], []
        for (d, v), op in zip(values, specs):
            vok = K.value_ok(d, v, ok)
            if op == "size":
                plan.append(("size", len(mcols), None))
                mcols.append(jnp.ones((cap,), jnp.float32))
                moks.append(ok)
                continue
            cnt_idx = len(mcols)
            mcols.append(jnp.ones((cap,), jnp.float32))
            moks.append(vok)
            if op == "count":
                plan.append(("count", cnt_idx, None))
            else:
                s_idx = len(mcols)
                mcols.append(d.astype(jnp.float32))
                moks.append(vok)
                plan.append((op, cnt_idx, s_idx))
        live = seg < ng_cap
        sums = PK.dense_accumulate(
            jnp.where(live, seg, 0).astype(jnp.int32), mcols,
            [m & live for m in moks], ng_cap)
        gvals = []
        for op, cnt_idx, s_idx in plan:
            if op in ("size", "count"):
                gvals.append((sums[cnt_idx].astype(jnp.int64), None))
            elif op == "sum":
                gvals.append((sums[s_idx], None))
            else:  # mean
                cnt = sums[cnt_idx]
                m = sums[s_idx] / jnp.maximum(cnt, 1.0)
                gvals.append((jnp.where(cnt > 0, m, jnp.nan), None))
        gvals = tuple(gvals)
    else:
        gvals = tuple(_segment_agg(op, data, valid, seg, ok, ng_cap)
                      for (data, valid), op in zip(values, specs))
    return gkeys, gvals, gvalid


@bounded_jit(static_argnames=("out_capacity",))
def _groupby_hashed_sort(gkeys, gvals, gvalid, out_capacity: int):
    """Sort the group table by keys ascending and emit [out_capacity]
    outputs packed at the front (pandas sort=True)."""
    ng_cap = gvalid.shape[0]
    operands: list = []
    for a in gkeys:
        operands.extend(SE.key_operands(a, None, padmask=gvalid))
    gperm = SE.stable_argsort(operands)

    def scatter(a):
        z = jnp.zeros((out_capacity,), dtype=a.dtype)
        src = a[gperm]
        m = min(ng_cap, out_capacity)
        return z.at[:m].set(src[:m])

    out_keys = tuple((scatter(a), None) for a in gkeys)
    out_vals = tuple((scatter(d), None if v is None else scatter(v))
                     for d, v in gvals)
    return out_keys, out_vals


def groupby_local_hashed_static(arrays, count, specs: Tuple[str, ...],
                                out_capacity: int, num_keys: int):
    """Fully-traced hash groupby for use INSIDE shard_map/jit bodies
    (distributed stage 1): same contract as `groupby_local` plus a
    traced `unresolved` flag, with the group segment space fixed at
    `out_capacity` instead of host-synced from the live group count
    (no host round-trip is possible inside a trace). The caller must
    guarantee out_capacity ≥ the true group count — with
    out_capacity == row capacity that holds by construction.

    Returns (out_keys, out_vals, n_groups, unresolved)."""
    seg, group_row, ok, n_groups, unresolved = _groupby_hashed_claim(
        arrays[:num_keys], count)
    gkeys, gvals, gvalid = _groupby_hashed_agg(
        arrays, seg, group_row, ok, specs, num_keys, out_capacity)
    out_keys, out_vals = _groupby_hashed_sort(gkeys, gvals, gvalid,
                                              out_capacity)
    return out_keys, out_vals, n_groups, unresolved


def groupby_local_hashed(arrays, count, specs: Tuple[str, ...],
                         out_capacity: int, num_keys: int):
    """Local groupby via the scatter-claim hash table (ops/hashtable.py)
    instead of a full-row sort: rows claim dense group ids in a few
    scatter/gather rounds, aggregates run as segment reductions (or the
    Pallas MXU one-hot accumulate when the group count fits) over the
    UNSORTED rows, and only the ~n_groups-row group table is sorted to
    restore pandas' key-ascending output — O(U log U) instead of
    O(N log N) with U = number of groups (the reference's hash-groupby
    advantage, bodo/libs/groupby/_groupby.cpp, realized with XLA
    scatters instead of serial chains).

    Same contract as groupby_local, plus an `unresolved` flag: True
    means the probe-round cap was hit (pathological input) and the
    caller must fall back to the sort kernel."""
    from bodo_tpu.table.table import round_capacity

    seg, group_row, ok, n_groups, unresolved = _groupby_hashed_claim(
        arrays[:num_keys], count)
    ng, unres = jax.device_get((n_groups, unresolved))
    if bool(unres):
        return None, None, 0, True
    cap = arrays[0][0].shape[0]
    ng_cap = min(round_capacity(max(int(ng), 1)), cap)
    gkeys, gvals, gvalid = _groupby_hashed_agg(
        arrays, seg, group_row, ok, specs, num_keys, ng_cap)
    out_keys, out_vals = _groupby_hashed_sort(gkeys, gvals, gvalid,
                                              out_capacity)
    return out_keys, out_vals, int(ng), False
