"""Table-level relational operators (the physical-op layer).

This is the analogue of the reference's physical operator set
(bodo/pandas/physical/*.h — project/filter/join/aggregate/sort) driving
the C++ streaming pipelines (bodo/pandas/_executor.h:76). Here each
operator is a host function over `Table` that dispatches cached jitted
kernels; REP tables run the local kernel, 1D tables run the shard_map
pipeline with explicit collectives. Dynamic result sizes use the
count-sync + capacity-bucket pattern: kernels return device row counts,
the host reads them (one scalar sync per pipeline stage, the analogue of
the reference's batch-size bookkeeping) and retries with a larger
capacity on overflow.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bodo_tpu.config import config
from bodo_tpu.ops import kernels as K
from bodo_tpu.ops.groupby import (agg_descale_factor, agg_dtype,
                                  groupby_local, result_dtype)
from bodo_tpu.ops.hashing import dest_shard, hash_columns
from bodo_tpu.ops.join import join_count, join_local
from bodo_tpu.ops.sort import sort_local, sort_sharded
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.parallel.shuffle import (_mesh_key, _MESHES, groupby_sharded,
                                       shuffle_rows)
from bodo_tpu.plan.expr import Expr, eval_expr, infer_dtype
from bodo_tpu.plan.fusion import (exchange, fusion_stage, groupby_route,
                                  join_build_skipped, join_emitted,
                                  join_route)
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.dict_utils import unify_dictionaries
from bodo_tpu.table.table import Column, ONED, REP, Table, round_capacity

from bodo_tpu.utils.kernel_cache import (KERNEL_CACHE_SIZE, KernelCache,
                                         named_jit)

# relational cache keys are ("kind", schema/dist/mesh/static parts...):
# the generic facet split in the observatory attributes retraces per kind
_jit_cache = KernelCache(maxsize=KERNEL_CACHE_SIZE,
                         subsystem="relational")


def _schema(t: Table) -> Dict[str, dt.DType]:
    return {n: c.dtype for n, c in t.columns.items()}


def _as_local(t: Table) -> Optional[Table]:
    """A 1-shard 'distributed' table is just a local table — return the
    zero-copy REP view so single-chip runs skip shuffle/combine stages
    entirely (the common case for the single-device benchmark)."""
    if t.distribution == ONED and t.num_shards == 1:
        return Table(dict(t.columns), t.nrows, REP, None)
    return None


def _keep_vranges(res: Table, src: Table) -> Table:
    """Row-preserving ops (filter/sort/shuffle/slice) keep host value
    bounds: values are a permutation/subset of the source, so the
    source's (lo, hi) bound still holds."""
    for n, c in res.columns.items():
        s = src.columns.get(n)
        if c.vrange is None and s is not None and s.dtype is c.dtype:
            c.vrange = s.vrange
    return res


def _dicts(t: Table) -> Dict[str, np.ndarray]:
    return {n: c.dictionary for n, c in t.columns.items()
            if c.dictionary is not None}


_dict_fp_cache: Dict[int, Tuple] = {}  # id -> (weakref, fingerprint)


def _dict_fp(d: Optional[np.ndarray]) -> int:
    if d is None:
        return 0
    ent = _dict_fp_cache.get(id(d))
    if ent is not None and ent[0]() is d:  # guard against id reuse after GC
        return ent[1]
    import weakref
    fp = hash(d.tobytes())
    key = id(d)
    _dict_fp_cache[key] = (weakref.ref(
        d, lambda _: _dict_fp_cache.pop(key, None)), fp)
    return fp


def _sig(t: Table) -> Tuple:
    """Schema signature for kernel caching (dict contents included because
    string predicates bake the dictionary LUT into the trace)."""
    return tuple((n, c.dtype.name, c.valid is not None,
                  _dict_fp(c.dictionary)) for n, c in t.columns.items())


# ---------------------------------------------------------------------------
# projection / assignment
# ---------------------------------------------------------------------------

from bodo_tpu.utils import tracing
from bodo_tpu.utils.tracing import traced_table_op as _traced


def _governed(name):
    """Reserve governor budget for a whole-table state-materializing
    operator (admission control; see runtime/memory_governor.py). The
    reservation sizes from the input tables' device bytes and spans the
    call; nested operator re-entry is a no-op inside reserve()."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **k):
            from bodo_tpu.runtime.memory_governor import (
                reserve, table_device_bytes)
            nbytes = sum(table_device_bytes(x) for x in a
                         if isinstance(x, Table))
            with reserve(name, nbytes):
                return fn(*a, **k)
        return wrapper
    return deco


def _inject_collective(*tables: Table, op: str = "collective") -> None:
    """Host-level `collective` fault point at the sharded-op dispatchers.

    The hooks inside parallel/collectives.py fire at trace time only
    (kernels are cached), so chaos tests arm THIS point: it fires once
    per distributed groupby/sort/join call when any input is ONED.

    Under BODO_TPU_LOCKSTEP the dispatch is additionally fingerprinted
    (`op` + user call site + sequence number) and cross-checked against
    peer processes, so a rank that diverged into a different collective
    raises a structured LockstepError instead of wedging the gang
    (analysis/lockstep.py).

    The comm observatory (parallel/comm.py) accounts the dispatch:
    input bytes + the lockstep peer-wait (arrival skew). No wall span
    here — the surrounding whole-op wall is compute-dominated and would
    corrupt the comm share; true transfer walls come from the
    shuffle_by_key / gather / scatter spans."""
    if any(isinstance(x, Table) and x.distribution == ONED
           and x.num_shards > 1 for x in tables):
        from bodo_tpu.runtime.resilience import maybe_inject
        maybe_inject("collective")
        from bodo_tpu.analysis import lockstep
        wait = lockstep.pre_collective(op)
        if config.comm_accounting:
            from bodo_tpu.parallel import comm
            comm.record(op, bytes_in=sum(
                comm.table_bytes(x) for x in tables
                if isinstance(x, Table)), wait_s=wait)


@_traced
def assign_columns(t: Table, new: Dict[str, Expr]) -> Table:
    """Add/replace columns computed from expressions (df.assign analogue).

    Top-level DictMap expressions (string→string transforms) are handled
    host-side: the translation runs on the dictionary, the device only
    remaps codes."""
    from bodo_tpu.plan.expr import (MAX_CONCAT_DICT, CodeLUT, ColRef,
                                    DictMap, Expr as _Expr, NestedFn,
                                    StrConcat, StrToList, ToChar,
                                    eval_expr as _eval)
    dictmaps = {n: e for n, e in new.items() if isinstance(e, DictMap)}
    strcats = {n: e for n, e in new.items() if isinstance(e, StrConcat)}
    strsplits = {n: e for n, e in new.items() if isinstance(e, StrToList)}
    nestedfns = {n: e for n, e in new.items() if isinstance(e, NestedFn)}
    tochars = {n: e for n, e in new.items() if isinstance(e, ToChar)}
    new = {n: e for n, e in new.items()
           if n not in dictmaps and n not in strcats
           and n not in strsplits and n not in nestedfns
           and n not in tochars}
    # a CodeLUT nested under Where/BinOp (e.g. IFF(c, MONTHNAME(d),
    # DAYNAME(d))) would evaluate to raw LUT codes with no dictionary
    # attached — reject loudly rather than decode garbage downstream.
    # CodeLUT as the (DictMap*) operand of a string-CONSUMING node
    # (StrPredicate/StrLen/StrHostFn/StrCodes evaluate the LUT at the
    # dictionary level) is legal; the walk still scans INSIDE consumer
    # operands for deeper illegal nesting.
    from bodo_tpu.plan.expr import codelut_misplaced as _codelut_bad
    for n, e in new.items():
        if _codelut_bad(e):
            raise NotImplementedError(
                "CodeLUT (MONTHNAME/DAYNAME) nested under "
                f"{type(e).__name__} is not supported as a projection")
    dm_cols: Dict[str, Column] = {}

    def _str_part(e):
        """Resolve a string-producing expr to (vals, codes, valid)."""
        chain = []
        base = e
        while isinstance(base, DictMap):
            chain.append(base)
            base = base.operand
        if isinstance(base, ColRef):
            src = t.columns[base.name]
            if src.dtype is not dt.STRING:
                raise NotImplementedError(
                    f"string function over non-string column "
                    f"{base.name!r} ({src.dtype.name}) — cast to varchar "
                    f"is not supported")
            vals = list(src.dictionary if src.dictionary is not None else [])
            data, valid = src.data, src.valid
        elif isinstance(base, CodeLUT):
            data, valid = _eval(base, t.device_data(), _dicts(t), _schema(t))
            vals = list(base.sorted_dict())
        else:
            raise TypeError(f"unsupported string part {base}")
        ok = None
        for tr in reversed(chain):
            # null-producing transforms (regexp_substr no-match, get
            # out-of-range): record per-entry validity before mapping
            hit = [not tr.host_null(s) for s in vals]
            if not all(hit):
                ok = hit if ok is None else [a & b for a, b in zip(ok, hit)]
            vals = [tr.apply_host(s) for s in vals]
        if ok is not None and not all(ok):
            lut = jnp.asarray(np.asarray(ok, dtype=bool))
            okv = lut[jnp.clip(data, 0, max(len(vals) - 1, 0))]
            valid = okv if valid is None else (valid & okv)
        return vals, data, valid

    for n, e in strcats.items():
        # mixed-radix codes over the per-part dictionaries; the combined
        # dictionary is their cross product (host-side, gated)
        col_parts = []   # (vals, codes, valid)
        layout = []      # str literal | index into col_parts
        for p in e.parts:
            if isinstance(p, str):
                layout.append(p)
            elif isinstance(p, _Expr):
                layout.append(len(col_parts))
                col_parts.append(_str_part(p))
            else:
                raise TypeError(f"bad concat part {p!r}")
        import math as _math
        total = _math.prod(max(len(v), 1) for v, _, _ in col_parts)
        if total > MAX_CONCAT_DICT:
            raise NotImplementedError(
                f"concat dictionary cross-product too large ({total})")
        import itertools
        combos = itertools.product(
            *[v if len(v) else [""] for v, _, _ in col_parts])
        combined = np.array(
            ["".join(item if isinstance(item, str) else combo[item]
                     for item in layout)
             for combo in combos], dtype=str)
        nd, remap = (np.unique(combined, return_inverse=True)
                     if len(combined) else (combined, np.zeros(0, np.int64)))
        code = None
        valid = None
        stride = total
        for vals, d, v in col_parts:
            k = max(len(vals), 1)
            stride //= k
            term = jnp.clip(d.astype(jnp.int64), 0, k - 1) * stride
            code = term if code is None else code + term
            if v is not None:
                valid = v if valid is None else (valid & v)
        if code is None:  # all-literal concat
            code = jnp.zeros((t.capacity,), jnp.int64)
        mp = jnp.asarray(remap.astype(np.int32) if len(remap)
                         else np.zeros(1, np.int32))
        dm_cols[n] = Column(mp[code], valid, dt.STRING, nd)

    for n, e in nestedfns.items():
        # semi-structured access: host-dictionary LUT kernels
        from bodo_tpu.table import nested as _nested
        base = e.operand
        if not isinstance(base, ColRef):
            raise TypeError("nested access must apply to a column")
        src = t.columns[base.name]
        if not dt.is_nested(src.dtype):
            raise TypeError(f"{base.name} is not a nested column "
                            f"({src.dtype.name})")
        if e.kind == "list_len":
            data, valid = _nested.list_lengths(src)
            dm_cols[n] = Column(data, valid, dt.INT64, None)
        elif e.kind == "list_get":
            dm_cols[n] = _nested.list_get(src, int(e.params[0]))
        elif e.kind == "field":
            if src.dtype.kind == "map":
                dm_cols[n] = _nested.map_get(src, e.params[0])
            else:
                dm_cols[n] = _nested.struct_field(src, e.params[0])
        else:
            raise ValueError(e.kind)

    for n, e in strsplits.items():
        # str.split(expand=False): split each dictionary entry, encode
        # the distinct result tuples as a list<string> dictionary
        vals, data, valid = _str_part(e.operand)
        parts = [e.split_host(s) for s in vals]
        uniq = sorted(set(parts))
        index = {v: i for i, v in enumerate(uniq)}
        remap = np.array([index[p] for p in parts] or [0], dtype=np.int32)
        codes = jnp.asarray(remap)[jnp.clip(data, 0, max(len(vals) - 1, 0))]
        dic_obj = np.empty(len(uniq), dtype=object)
        for i, v in enumerate(uniq):
            dic_obj[i] = v
        dm_cols[n] = Column(codes, valid, dt.list_of(dt.STRING), dic_obj)

    for n, e in tochars.items():
        # TO_CHAR/TO_VARCHAR: evaluate the operand on device, format on
        # host once, dict-encode like any string ingest
        from bodo_tpu.plan.expr import infer_dtype as _infer
        if _infer(e.operand, _schema(t)) is dt.STRING:
            # identity on strings (dictionary passes through)
            vals, data, valid = _str_part(e.operand)
            mapped = np.array(vals, dtype=str)
            nd, remap = (np.unique(mapped, return_inverse=True)
                         if len(mapped)
                         else (mapped, np.zeros(0, np.int64)))
            mp = jnp.asarray(remap.astype(np.int32) if len(remap)
                             else np.zeros(1, np.int32))
            dm_cols[n] = Column(
                mp[jnp.clip(data, 0, max(len(vals) - 1, 0))], valid,
                dt.STRING, nd if len(nd) else np.array([""], str))
            continue
        d, v = _eval(e.operand, t.device_data(), _dicts(t), _schema(t))
        # format only the LIVE rows: padding would waste host formatting
        # and inject phantom dictionary entries ('0', '1970-01-01') —
        # or crash outright on garbage tail values
        vals = np.asarray(jax.device_get(d))[:t.nrows]
        host_v = (np.asarray(jax.device_get(v))[:t.nrows]
                  if v is not None else np.ones(len(vals), bool))
        src_dt = infer_dtype(e.operand, _schema(t))
        fmt = e.strftime_fmt()
        if fmt is not None and src_dt not in (dt.DATETIME, dt.DATE):
            raise NotImplementedError(
                f"TO_CHAR format {e.fmt!r} is only supported for "
                f"date/datetime operands (got {src_dt.name})")
        if src_dt is dt.DATETIME or src_dt is dt.DATE:
            unit = "ns" if src_dt is dt.DATETIME else "D"
            ts = vals.astype(f"datetime64[{unit}]")
            import pandas as _pd
            ser = _pd.Series(ts)
            out = ser.dt.strftime(
                fmt or ("%Y-%m-%d" if src_dt is dt.DATE
                        else "%Y-%m-%d %H:%M:%S.%f")).to_numpy(str)
        elif dt.is_decimal(src_dt):
            # decimals store value*10^scale in int64 — format exactly
            # (integer divmod, no float round-trip)
            sc = src_dt.scale

            def _fmtd(x):
                sign = "-" if x < 0 else ""
                q, rem = divmod(abs(int(x)), 10 ** sc)
                return f"{sign}{q}.{rem:0{sc}d}" if sc else f"{sign}{q}"
            out = np.array([_fmtd(x) for x in vals.astype(np.int64)],
                           dtype=str)
        elif np.issubdtype(vals.dtype, np.floating):
            # Snowflake canonical float rendering (repr-shortest)
            out = np.array([repr(float(x)) for x in vals], dtype=str)
        elif vals.dtype == np.bool_:
            out = np.where(vals, "true", "false").astype(str)
        else:
            out = vals.astype(np.int64).astype(str)
        uniq, inv = (np.unique(out, return_inverse=True) if len(out)
                     else (np.array([], str), np.zeros(0, np.int64)))
        cdata = np.zeros(t.capacity, np.int32)
        cdata[:len(inv)] = inv.astype(np.int32)
        vm = None
        if v is not None:
            vmn = np.zeros(t.capacity, bool)
            vmn[:len(host_v)] = host_v
            vm = jnp.asarray(vmn)
        dm_cols[n] = Column(jnp.asarray(cdata), vm, dt.STRING,
                            uniq if len(uniq) else np.array([""], str))

    for n, e in dictmaps.items():
        # compose nested transforms (upper(substring(...))) down to the
        # base column/CodeLUT, mirroring the StrPredicate eval path
        vals, data, valid = _str_part(e)
        mapped = np.array(vals, dtype=str)
        nd, remap = (np.unique(mapped, return_inverse=True)
                     if len(mapped) else (mapped, np.zeros(0, np.int64)))
        mp = jnp.asarray(remap.astype(np.int32) if len(remap)
                         else np.zeros(1, np.int32))
        codes = mp[jnp.clip(data, 0, max(len(vals) - 1, 0))]
        dm_cols[n] = Column(codes, valid, dt.STRING, nd)

    schema = _schema(t)
    dicts = _dicts(t)
    if new:
        key = ("assign", _sig(t), tuple((n, e.key()) for n, e in new.items()),
               t.distribution)
        fn = _jit_cache.get(key)
        if fn is None:
            exprs = dict(new)

            @partial(named_jit, "project")
            def fn(tree):
                # return ONLY the new columns: passing untouched inputs
                # through a jitted function copies them (no donation) —
                # a full-table memcpy per assign on wide tables
                out = {}
                cap = next(iter(tree.values()))[0].shape[0]
                for name, e in exprs.items():
                    d, v = eval_expr(e, tree, dicts, schema)
                    if d.ndim == 0:  # literal projection → broadcast
                        d = jnp.broadcast_to(d, (cap,))
                    out[name] = (d, v)
                return out
            _jit_cache[key] = fn
        new_tree = fn(t.device_data())
        dtypes = {n: infer_dtype(e, schema) for n, e in new.items()}
        cols = dict(t.columns)  # untouched columns: same device arrays
        for n in new:
            d, v = new_tree[n]
            cols[n] = Column(d, v, dtypes[n], None)
        res = Table(cols, t.nrows, t.distribution, t.counts)
        # dictionary propagation: renames keep the source dictionary,
        # numeric outputs drop stale dictionaries
        from bodo_tpu.plan.expr import expr_range
        for n, e in new.items():
            c = res.columns[n]
            dict_typed = c.dtype is dt.STRING or dt.is_nested(c.dtype)
            if isinstance(e, CodeLUT):
                res.columns[n] = Column(c.data.astype(np.int32), c.valid,
                                        dt.STRING, e.sorted_dict())
            elif dict_typed and isinstance(e, ColRef):
                res.columns[n] = Column(c.data, c.valid, c.dtype,
                                        t.columns[e.name].dictionary)
            elif not dict_typed:
                res.columns[n] = Column(c.data, c.valid, c.dtype, None,
                                        expr_range(e, t.columns))
        # untouched columns keep their host-known value bounds
        for n, c in t.columns.items():
            if n in res.columns and n not in new and n not in dm_cols and \
                    res.columns[n].vrange is None:
                res.columns[n].vrange = c.vrange
    else:
        res = t.with_columns(t.columns)
    for n, c in dm_cols.items():
        res.columns[n] = c
    return res


def select_columns(t: Table, names: Sequence[str]) -> Table:
    return t.select(list(names))


def assign_categorical(t: Table, name: str, code_expr: Expr,
                       categories: Sequence[str]) -> Table:
    """Add a string column from an integer code expression + category list
    (the device-side analogue of `Series.map({...})` onto strings: strings
    never touch the device, only their codes do).

    `code_expr` must produce indices into `sorted(categories)`.
    """
    cats = np.asarray(sorted(categories), dtype=str)
    res = assign_columns(t, {name: code_expr})
    c = res.columns[name]
    res.columns[name] = Column(c.data.astype(np.int32), c.valid, dt.STRING,
                               cats)
    return res


def category_code(categories: Sequence[str], value: str) -> int:
    """Code of `value` in the sorted-category dictionary."""
    return int(np.searchsorted(np.asarray(sorted(categories)), value))


# ---------------------------------------------------------------------------
# filter
# ---------------------------------------------------------------------------

@_traced
def filter_table(t: Table, predicate: Expr) -> Table:
    """Filter rows; null predicate counts as False (SQL semantics)."""
    schema = _schema(t)
    dicts = _dicts(t)
    names = t.names
    m = mesh_mod.get_mesh()
    key = ("filter", _mesh_key(m), _sig(t), predicate.key(), t.distribution)
    fn = _jit_cache.get(key)
    if fn is None:
        def body(tree, count):
            cap = tree[names[0]][0].shape[0]
            mask, mv = eval_expr(predicate, tree, dicts, schema)
            if mv is not None:
                mask = mask & mv
            mask = mask & K.row_mask(count, cap)
            flat = []
            for n in names:
                d, v = tree[n]
                flat.append(d)
                flat.append(v)
            out, cnt = K.compact(mask, tuple(flat))
            out_tree = {n: (out[2 * i], out[2 * i + 1])
                        for i, n in enumerate(names)}
            return out_tree, cnt

        if t.distribution == ONED:
            m = mesh_mod.get_mesh()
            ax = config.data_axis

            def sharded(tree, counts):
                out_tree, cnt = body(tree, counts[0])
                return out_tree, cnt[None]
            fn = named_jit("filter", C.smap(
                sharded, in_specs=(P(ax), P(ax)), out_specs=(P(ax), P(ax)),
                mesh=m))
        else:
            def rep(tree, count):
                return body(tree, count)
            fn = named_jit("filter", rep)
        _jit_cache[key] = fn

    if t.distribution == ONED:
        out_tree, cnts = fn(t.device_data(), t.counts_device())
        counts = np.asarray(jax.device_get(cnts)).astype(np.int64)
        return _keep_vranges(
            rebucket(t.with_device_data(out_tree, nrows=int(counts.sum()),
                                        counts=counts)), t)
    out_tree, cnt = fn(t.device_data(), jnp.asarray(t.nrows))
    return _keep_vranges(rebucket(t.with_device_data(out_tree,
                                                     nrows=int(cnt))), t)


# ---------------------------------------------------------------------------
# key packing (multi-key → one int64 when ranges fit)
# ---------------------------------------------------------------------------

def _key_ranges(t: Table, keys: Sequence[str], use_bounds: bool = True):
    """Host-known (lo, hi) range per key column, or None when unpackable.
    Strings use the dictionary size; bools are 0/1; ints/dates use the
    column's host-known bound (`Column.vrange` — parquet stats / static
    field ranges) when present, else reduce min/max on device. Returns
    (ranges, inexact): `inexact` holds the positions served from bounds
    — callers whose gates fail on a bound call `_refine_ranges` to get
    the exact span before giving up."""
    ranges = []
    inexact = set()
    need_reduce = []
    for i, k in enumerate(keys):
        c = t.column(k)
        if c.dtype is dt.STRING:
            ranges.append((0, max(len(c.dictionary) - 1, 0))
                          if c.dictionary is not None else None)
        elif c.dtype.kind == "b":
            ranges.append((0, 1))
        elif c.dtype.kind in ("i", "u") or c.dtype in (dt.DATE,):
            if use_bounds and c.vrange is not None:
                ranges.append((int(c.vrange[0]), int(c.vrange[1])))
                # tight bounds (parquet scan stats) are not worth an
                # exact re-reduce; loose ones (static field ranges like
                # month 1..12) are refinable on a gate near-miss
                if not (len(c.vrange) > 2 and c.vrange[2]):
                    inexact.add(i)
            else:
                ranges.append("reduce")
                need_reduce.append(k)
        else:  # floats/datetimes: don't pack
            ranges.append(None)
    if need_reduce:
        if t.nrows == 0:
            stats = {f"{k}__min": 0 for k in need_reduce}
            stats.update({f"{k}__max": 0 for k in need_reduce})
        else:
            specs = [(k, "min", f"{k}__min") for k in need_reduce] + \
                [(k, "max", f"{k}__max") for k in need_reduce]
            stats = reduce_table(t, specs)
        it = iter(need_reduce)
        for i, r in enumerate(ranges):
            if r == "reduce":
                k = next(it)
                lo = _range_int(stats[f"{k}__min"])
                hi = _range_int(stats[f"{k}__max"])
                ranges[i] = None if lo is None or hi is None else (lo, hi)
    return ranges, inexact


def _refine_ranges(t: Table, keys: Sequence[str], ranges, inexact):
    """Replace bound-derived entries with exact device-reduced spans."""
    if not inexact:
        return ranges, set()
    exact, _ = _key_ranges(t, [keys[i] for i in sorted(inexact)],
                           use_bounds=False)
    out = list(ranges)
    for i, r in zip(sorted(inexact), exact):
        out[i] = r
    return out, set()


def _range_int(v) -> Optional[int]:
    """Reduce-scalar → int for packing (DATE min/max comes back as a
    datetime64/date scalar — convert to epoch days)."""
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, np.datetime64):
        return int(v.astype("datetime64[D]").astype(np.int64))
    import datetime
    if isinstance(v, datetime.date) and not isinstance(v, datetime.datetime):
        return int((np.datetime64(v, "D") - np.datetime64(0, "D"))
                   .astype(np.int64))
    if isinstance(v, (bool, np.bool_)):
        return int(v)
    return None  # floats / NaN / anything else: don't pack


def _pack_plan(t: Table, keys: Sequence[str], max_bits: int = 62,
               ranges=None):
    """Packing layout [(name, lo, bits, shift)] or None. One extra code
    per field is reserved for null keys (so dropna still works)."""
    if not config.pack_keys or len(keys) < 2:
        return None
    inexact = set()
    if ranges is None:
        ranges, inexact = _key_ranges(t, keys)

    def layout(rs):
        fields = []
        total = 0
        for k, r in zip(keys, rs):
            if r is None:
                return None
            lo, hi = r
            span = hi - lo + 2  # +1 for the null/sentinel code
            bits = max(1, int(span - 1).bit_length())
            fields.append((k, lo, bits))
            total += bits
            if total > max_bits:
                return None
        return fields, total

    got = layout(ranges)
    if got is None and inexact and \
            not any(r is None for r in ranges):
        # loose bounds overflowed the bit budget — retry with exact spans
        ranges, inexact = _refine_ranges(t, keys, ranges, inexact)
        got = layout(ranges)
    if got is None:
        return None
    fields, total = got
    # first key in the TOP bits so packed ascending == lexicographic order
    plan = []
    shift = total
    for k, lo, bits in fields:
        shift -= bits
        plan.append((k, lo, bits, shift))
    return plan


def _pack_keys_kernel(tree, pack, count):
    """Packed int64 key + validity (False where any key is null)."""
    cap = next(iter(tree.values()))[0].shape[0]
    packed = jnp.zeros((cap,), dtype=jnp.int64)
    valid = jnp.ones((cap,), dtype=bool)
    for name, lo, bits, shift in pack:
        d, v = tree[name]
        ok = jnp.ones((cap,), dtype=bool) if v is None else v
        if jnp.issubdtype(d.dtype, jnp.floating):  # pragma: no cover
            ok = ok & ~jnp.isnan(d)
        code = jnp.clip(d.astype(jnp.int64) - lo, 0, (1 << bits) - 2)
        packed = packed | (jnp.where(ok, code, (1 << bits) - 1)
                           << np.int64(shift))
        valid = valid & ok
    return packed, valid


def _unpack_keys(packed, pack):
    out = {}
    for name, lo, bits, shift in pack:
        code = (packed >> np.int64(shift)) & np.int64((1 << bits) - 1)
        out[name] = code + lo
    return out


# ---------------------------------------------------------------------------
# groupby aggregate
# ---------------------------------------------------------------------------


def _agg_out_col(src: Column, op: str, vd, vv) -> Column:
    """Build an aggregation output Column: logical dtype from agg_dtype,
    decimal physical values descaled, kernel accumulator dtypes (f64
    quantiles, f32 MXU sums) cast to the declared dtype."""
    rdt = agg_dtype(op, src.dtype)
    f = agg_descale_factor(op, src.dtype)
    if f != 1.0:  # decimal physical -> logical float
        vd = vd.astype(np.float64) / f
    if vd.dtype != rdt.numpy:
        vd = vd.astype(rdt.numpy)
    return Column(vd, vv, rdt,
                  src.dictionary if rdt is dt.STRING else None)


@_traced
@_governed("groupby_agg")
def groupby_agg(t: Table, keys: Sequence[str],
                aggs: Sequence[Tuple[str, str, str]]) -> Table:
    """Group by `keys`; aggs = [(value_col, op, out_name)].
    Output sorted by keys ascending (pandas sort=True).

    Which keys take which realisation on a replicated table, first
    that fits (each opens its `fusion.groupby_route` span):
      dense   every key has a host-known range (ints, bools, dict
              codes) and the product of the ranges fits the slot
              budget: one slot a key combination, no sort
      packed  such keys whose product is too wide for slots but fits
              62 bits pack into one int64, and the group-by runs again
              on that one key — a single-operand sort replaces the
              multi-operand lexicographic sort and the shuffle moves
              one key column (the reference gets a similar effect from
              its categorical/sorted-key exscan strategies,
              bodo/libs/groupby/)
      hashed  any other key list without a float64 key, with HASH_OPS
              aggregates: the scatter-claim table, no row sort
      sort    the rest, and every key list that holds a float64: the
              hashed route's codes are the key's IEEE bits
              (`hashtable.encode_columns`), a bitcast the TPU compiler
              refuses for float64 (it holds a double as two floats),
              while the sort compares a float64 natively
              (`sort_encoding.encode_field`). The gate is the same on
              every platform, so the CPU tests run the path the chip
              runs. NaN keys are dropped and -0.0 == 0.0 either way."""
    _inject_collective(t, op="groupby_agg")
    keys = list(keys)
    # normalize op aliases: median/quantile_<q> → the "q:<q>" kernel op
    def _norm(op: str) -> str:
        if op == "median":
            return "q:0.5"
        if op.startswith("quantile_"):
            return f"q:{float(op[len('quantile_'):])}"
        return op
    aggs = [(c, _norm(op), o) for c, op, o in aggs]

    if any(op.startswith(("listagg", "listaggd")) for _, op, _ in aggs):
        return _groupby_agg_with_listagg(t, keys, aggs)

    local = _as_local(t)
    if local is not None:
        return groupby_agg(local, keys, aggs)

    # non-decomposable aggs (nunique, quantiles) can't two-phase combine:
    # co-locate whole groups with one hash shuffle, then finish locally
    from bodo_tpu.ops.groupby import DECOMPOSE
    if t.distribution == ONED and any(
            op not in DECOMPOSE for _, op, _ in aggs):
        return _groupby_agg_colocated(t, keys, aggs)

    # cheap host gates first: _key_ranges does a blocking device reduce
    dense_ok = (t.distribution == REP and config.dense_groupby_max_slots > 0
                and not any(op in ("nunique", "mode") or op.startswith("q:")
                            for _, op, _ in aggs))
    want_ranges = bool(keys) and (
        dense_ok or (config.pack_keys and len(keys) >= 2))
    ranges, inexact = _key_ranges(t, keys) if want_ranges else (None, set())

    def _dense_slots(rs) -> int:
        n = 1
        for lo, hi in rs:  # python ints: no overflow on wild ranges
            n *= int(hi) - int(lo) + 1
            if n > config.dense_groupby_max_slots:
                break
        return n

    if dense_ok and ranges is not None and \
            all(r is not None for r in ranges):
        n_slots = _dense_slots(ranges)
        # dense pays a fixed O(n_slots) cost — only worth it when the slot
        # space isn't much larger than the input
        gate = (0 < n_slots <= config.dense_groupby_max_slots and
                n_slots <= 2 * max(t.nrows, 1))
        if not gate and inexact:
            # loose bounds may have inflated the slot product past the
            # gate — one exact reduce is cheaper than losing the dense
            # path on a near-miss
            ranges, inexact = _refine_ranges(t, keys, ranges, inexact)
            n_slots = _dense_slots(ranges)
            gate = (0 < n_slots <= config.dense_groupby_max_slots and
                    n_slots <= 2 * max(t.nrows, 1))
        if gate:
            return _groupby_agg_dense(t, keys, list(aggs), ranges)

    pack = _pack_plan(t, keys, 62,
                      ranges=None if inexact else ranges)
    if pack is not None:
        with groupby_route("packed", len(keys), t.nrows):
            return _groupby_agg_packed(t, keys, list(aggs), pack)
    specs = tuple(op for _, op, _ in aggs)
    val_names = [c for c, _, _ in aggs]

    def _arrays(t: Table):
        return tuple((t.column(n).data, t.column(n).valid)
                     for n in keys + val_names)
    arrays = _arrays(t)

    # arbitrary-cardinality hash path (scatter-claim table): no row
    # sort; only the group table is sorted. Falls back to the sort
    # kernel on probe-round exhaustion (pathological keys).
    # Not for a float64 key: its code would be the double's bits, which
    # the TPU compiler cannot take (the docstring says who sorts it).
    from bodo_tpu.ops.groupby import HASH_OPS, groupby_local_hashed
    if (t.distribution == REP and keys and config.hash_groupby
            and all(op in HASH_OPS for op in specs)
            and not any(t.column(k).data.dtype == np.float64
                        for k in keys)):
        with groupby_route("hashed", len(keys), t.nrows):
            out_keys, out_vals, ng, unresolved = groupby_local_hashed(
                arrays, jnp.asarray(t.nrows), specs, t.capacity, len(keys))
        if not unresolved:
            cols: Dict[str, Column] = {}
            for kname, (kd, kv) in zip(keys, out_keys):
                src = t.column(kname)
                cols[kname] = Column(kd, kv, src.dtype, src.dictionary,
                                     src.vrange)
            for (cname, op, oname), (vd, vv) in zip(aggs, out_vals):
                cols[oname] = _agg_out_col(t.column(cname), op, vd, vv)
            return shrink_to_fit(Table(cols, ng, REP, None))

    # the row sort is sized to the rows, not to the capacity a join or a
    # filter left behind (Q18: 490 rows in lineitem's six million slots)
    t = shrink_to_fit(t.select(list(dict.fromkeys(keys + val_names))))
    arrays = _arrays(t)
    if t.distribution == ONED:
        # bucket/final capacities are sized by the host from stage-1
        # partial counts (with overflow retry) inside groupby_sharded
        (out_keys, out_vals), ngs, ovf = groupby_sharded(
            arrays, t.counts_device(), len(keys), specs)
        counts = np.asarray(jax.device_get(ngs)).reshape(-1).astype(np.int64)
        nrows, dist = int(counts.sum()), ONED
    else:
        with groupby_route("sort", len(keys), t.nrows):
            out_keys, out_vals, ng = groupby_local(
                arrays, jnp.asarray(t.nrows), specs, t.capacity, len(keys))
            nrows = int(ng)
        counts, dist = None, REP

    cols: Dict[str, Column] = {}
    for kname, (kd, kv) in zip(keys, out_keys):
        src = t.column(kname)
        cols[kname] = Column(kd, kv, src.dtype, src.dictionary, src.vrange)
    for (cname, op, oname), (vd, vv) in zip(aggs, out_vals):
        src = t.column(cname)
        cols[oname] = _agg_out_col(src, op, vd, vv)
    return shrink_to_fit(Table(cols, nrows, dist, counts))


def _groupby_agg_with_listagg(t: Table, keys, aggs) -> Table:
    """Groupby containing LISTAGG ("listagg[:<sep>]"): the concatenated
    per-group strings are host objects by construction (string data lives
    in host dictionaries), so the listagg columns finalize on host after
    the native aggs run, aligned to the native output's group order
    (reference: BodoSQL listagg kernel,
    BodoSQL/bodosql/kernels/listagg.py)."""
    la = [(c, op, o) for c, op, o in aggs
          if op.startswith(("listagg", "listaggd"))]
    rest = [(c, op, o) for c, op, o in aggs
            if not op.startswith(("listagg", "listaggd"))]
    # native part (a size placeholder keeps the group keys when listagg
    # is the only agg)
    base = rest or [(keys[0], "size", "__la_size")]
    out = groupby_agg(t, keys, base)
    gout = out.gather() if out.distribution == ONED else out
    okeys = gout.to_pandas()[list(keys)]
    # host finalize: within-group original row order (pandas groupby
    # preserves it, matching LISTAGG without WITHIN GROUP)
    src = t.gather() if t.distribution == ONED else t
    need = list(dict.fromkeys(list(keys) + [c for c, _, _ in la]))
    pdf = src.select(need).to_pandas()
    cols: Dict[str, Column] = dict(gout.columns)
    for c, op, o in la:
        sep = op.split(":", 1)[1] if ":" in op else ","
        dedup = op.startswith("listaggd")

        def _cat(v, s=sep, d=dedup):
            it = dict.fromkeys(v) if d else v
            return s.join(str(x) for x in it)
        ser = (pdf.dropna(subset=[c]).groupby(keys, sort=False)[c]
               .agg(_cat))
        aligned = okeys.merge(ser.rename(o), left_on=keys,
                              right_index=True, how="left")[o]
        vals = aligned.to_numpy(dtype=object)
        cols[o] = Column.from_numpy(vals, capacity=gout.capacity)
    if "__la_size" in cols and not any(o == "__la_size" for _, _, o in aggs):
        del cols["__la_size"]
    ordered = {o: cols[o] for _, _, o in
               [(k, None, k) for k in keys] + list(aggs)}
    return Table(ordered, gout.nrows, REP, None)


def _packed_key_table(t: Table, pack, with_valid: bool = True) -> Table:
    """Add the packed int64 key column '__packed' to `t` (jitted).

    with_valid=True attaches the any-key-null mask (groupby dropna);
    False leaves nulls encoded only as per-field sentinel codes, which is
    the correct lexicographic na_last behavior for sorting."""
    key_names = [name for name, *_ in pack]
    key = ("packkeys", _sig(t.select(key_names)), tuple(pack), with_valid)
    fn = _jit_cache.get(key)
    if fn is None:
        pk = tuple(pack)

        @partial(named_jit, "pack_keys")
        def fn(tree):
            return _pack_keys_kernel(tree, pk, None)
        _jit_cache[key] = fn
    packed, valid = fn({n: (t.column(n).data, t.column(n).valid)
                        for n in key_names})
    cols = dict(t.columns)
    cols["__packed"] = Column(packed, valid if with_valid else None,
                              dt.INT64, None)
    return Table(cols, t.nrows, t.distribution, t.counts)


def _groupby_agg_packed(t: Table, keys, aggs, pack) -> Table:
    tp = _packed_key_table(t, pack)
    val_cols = list(dict.fromkeys(c for c, _, _ in aggs))
    tp = tp.select(["__packed"] + val_cols)
    out = groupby_agg(tp, ["__packed"],
                      [(c, op, o) for c, op, o in aggs])
    # unpack key columns from the packed values (device, elementwise)
    key_un = ("unpack", tuple(pack), out.capacity)
    fn = _jit_cache.get(key_un)
    if fn is None:
        pk = tuple(pack)

        @partial(named_jit, "groupby_unpack_keys")
        def fn(packed):
            return _unpack_keys(packed, pk)
        _jit_cache[key_un] = fn
    unpacked = fn(out.column("__packed").data)
    cols: Dict[str, Column] = {}
    for name, lo, bits, shift in pack:
        src = t.column(name)
        d = unpacked[name]
        if src.dtype is dt.STRING:
            d = d.astype(np.int32)
        elif src.dtype.kind == "b":
            d = d.astype(bool)
        elif d.dtype != src.dtype.numpy:
            d = d.astype(src.dtype.numpy)
        cols[name] = Column(d, None, src.dtype, src.dictionary, src.vrange)
    for _, _, oname in aggs:
        cols[oname] = out.columns[oname]
    return Table(cols, out.nrows, out.distribution, out.counts)


def _dense_slots(key_arrays, los, sizes, mask, strict_range: bool = False):
    """Mixed-radix dense slot ids shared by the dense groupby and the
    dense-LUT join build/probe. Returns (slot int32[cap], live mask):
    null/NaN keys drop out of `mask`; with strict_range, rows whose key
    falls outside [lo, lo+size) (or is a non-integral float) drop too —
    the probe-side policy, where out-of-range just means no match."""
    cap = key_arrays[0][0].shape[0]
    slot = jnp.zeros((cap,), dtype=jnp.int32)
    for (d, v), lo, size in zip(key_arrays, los, sizes):
        if v is not None:
            mask = mask & v
        if jnp.issubdtype(d.dtype, jnp.floating):
            mask = mask & ~jnp.isnan(d)
            if strict_range:
                mask = mask & (d == jnp.floor(d))
        code = d.astype(jnp.int64) - lo
        if strict_range:
            mask = mask & (code >= 0) & (code < size)
        slot = slot * np.int32(size) + \
            jnp.clip(code, 0, size - 1).astype(jnp.int32)
    return slot, mask


@fusion_stage
def dense_agg_tail(tree, live, kn, vn, specs, sizes, los, n_slots: int,
                   use_mxu: bool):
    """Traced dense-groupby tail: give every `live` row its mixed-radix
    dense slot, aggregate per slot, then decode slot indices back into
    key columns and compact the present slots ascending. The per-slot
    aggregation takes one of three routes (`dense_route`): `mxu`, one
    one-hot matmul over float32 columns, when the caller's `use_mxu`
    gate (`dense_mxu_ok`, Pallas on) admits it; else `reduce`, masked
    tree reductions over the rows, one a slot, in the values' own
    dtype, when the slot space is at most `DENSE_REDUCE_MAX_SLOTS` and
    every spec is in `DENSE_REDUCE_OPS`; else `scatter`, one
    `segment_*` scatter pass a spec (`ops/groupby._segment_agg`).

    Shared between `_groupby_agg_dense` (live = row_mask(count)) and
    the whole-stage fusion agg stage (plan/fusion.py — live = the fused
    filter mask, so filtered rows never materialize before aggregation).
    Runs INSIDE a jitted program: no host sync is legal here (the
    shardcheck `fusion-host-call` lint enforces it via @fusion_stage).
    Returns (out_keys, out_vals_flat_pairs, n_groups)."""
    from bodo_tpu.ops import pallas_kernels as PK_
    from bodo_tpu.ops.groupby import _segment_agg
    cap = tree[kn[0]][0].shape[0]
    slot, padmask = _dense_slots([tree[n] for n in kn], los, sizes, live)
    route = dense_route(n_slots, specs, use_mxu)
    if route == "mxu":
        # one fused one-hot matmul: [present | per-spec columns]
        mcols, moks = [padmask.astype(jnp.float32)], [padmask]
        plan = []
        for c, op in zip(vn, specs):
            d, v = tree[c]
            ok = K.value_ok(d, v, padmask)
            if op == "size":
                plan.append(("size", 0, None))  # == present column
                continue
            cnt_idx = len(mcols)
            mcols.append(jnp.ones((cap,), jnp.float32))
            moks.append(ok)
            if op == "count":
                plan.append(("count", cnt_idx, None))
            elif op in ("sum", "mean"):
                s_idx = len(mcols)
                mcols.append(d.astype(jnp.float32))
                moks.append(ok)
                plan.append((op, cnt_idx, s_idx))
        sums = PK_.dense_accumulate(slot, mcols, moks, n_slots)
        present = sums[0] > 0
        outs = []
        for op, cnt_idx, s_idx in plan:
            if op == "size":
                outs.append((sums[0].astype(jnp.int64), None))
            elif op == "count":
                outs.append((sums[cnt_idx].astype(jnp.int64), None))
            elif op == "sum":
                outs.append((sums[s_idx], None))
            else:  # mean
                cnt = sums[cnt_idx]
                m = sums[s_idx] / jnp.maximum(cnt, 1.0)
                outs.append((jnp.where(cnt > 0, m, jnp.nan), None))
    elif route == "reduce":
        present, outs = _dense_reduce_aggs(tree, vn, specs, slot, padmask,
                                           n_slots)
    else:
        present = jax.ops.segment_sum(
            padmask.astype(jnp.int32), slot,
            num_segments=n_slots) > 0
        outs = [_segment_agg(op, tree[c][0], tree[c][1], slot,
                             padmask, n_slots)
                for c, op in zip(vn, specs)]
    # the present slots ascending: their indices ARE the keys (mixed-
    # radix decode of `src`, neither scattered nor gathered); only the
    # aggregates are gathered
    src, n_groups = K.compact_index(present)
    keep = K.row_mask(n_groups, n_slots)
    rem = src
    out_keys = [None] * len(kn)
    for i in range(len(kn) - 1, -1, -1):
        code = rem % np.int32(sizes[i])
        rem = rem // np.int32(sizes[i])
        out_keys[i] = jnp.where(
            keep, code.astype(jnp.int64) + np.int64(los[i]), 0)
    vflat, slots_v = _flatten_with_valids(outs)
    out_vals = _rebuild_from_flat(
        K.take_compacted(src, n_groups, tuple(vflat)), slots_v)
    return tuple(out_keys), tuple(out_vals), n_groups


def dense_mxu_ok(capacity: int, val_dtypes, specs) -> bool:
    """Gate for the MXU one-hot-matmul accumulate, shared with the
    fusion planner: f32 accumulation limits — sums/means only over
    float32-or-narrower float columns (int sums must stay exact in
    int64), counts only while the row capacity stays within f32's
    exact-integer range (2^24; `present` is also a count)."""
    def _ok(d, op):
        if op in ("count", "size"):
            return capacity <= (1 << 24)
        return jnp.issubdtype(d, jnp.floating) and np.dtype(d).itemsize <= 4
    return (capacity <= (1 << 24)
            and all(op in ("sum", "count", "size", "mean") for op in specs)
            and all(_ok(d, op) for d, op in zip(val_dtypes, specs)))


# The reduce route of `dense_agg_tail` costs rows x n_slots
# compare-select-accumulates an aggregate, the scatter route a fixed
# cost a row whatever the slots (66 ns a row a scatter at 6 slots, 153
# ns at 3.8M; v5e, ledger, PR 27). This is the largest slot count of
# the chip sweep (`chip_dense_sweep.py`: 6, 25, 64, 256, 1024 slots on
# the Q1-shaped tail, 3,000,064 rows, eight float64 specs) at which
# the reduce route was still at least twice as fast: at 1024 slots it
# read 0.149 s against the scatter route's 3.41 s, so the crossover
# lies above the sweep. The table is in PERF.md section 6 (PR 28).
DENSE_REDUCE_MAX_SLOTS = 1024

# specs the reduce route implements; any other keeps the whole tail on
# the scatter route, as `dense_mxu_ok` keeps it off the MXU
DENSE_REDUCE_OPS = frozenset(
    ("sum", "sumnull", "sum64", "count", "size", "mean", "min", "max"))


def dense_route(n_slots: int, specs, use_mxu: bool) -> str:
    """Which realisation `dense_agg_tail` takes for this shape: `mxu`,
    `reduce` or `scatter` (its docstring says what each is). Callers
    put it on their span and into their program-cache key."""
    if use_mxu:
        return "mxu"
    if n_slots <= DENSE_REDUCE_MAX_SLOTS and \
            all(op in DENSE_REDUCE_OPS for op in specs):
        return "reduce"
    return "scatter"


def _dense_reduce_aggs(tree, vn, specs, slot, padmask, n_slots: int):
    """The reduce route of `dense_agg_tail`: for each slot g, every
    aggregate is `reduce(where(slot == g & ok, v, identity))` over the
    rows, in the dtype `_segment_agg` accumulates in (float64 sums stay
    float64, int sums int64; counts are int32 sums widened at the end).
    Distinct terms are computed once: one count per distinct `ok` mask,
    one sum per (column, dtype) shared by `sum` and `mean`. The `vmap`
    over slots keeps compare and select inside the reductions' fusion:
    nothing of [rows, n_slots] is materialised.
    Returns (present bool[n_slots], [(data, valid)] per spec)."""
    masks = {None: padmask}  # ok-mask key -> mask; None: every live row
    terms = {}  # term key -> (mask key, values, identity, reducer)

    def count_term(mk):
        terms.setdefault(("cnt", mk), (mk, np.int32(1), np.int32(0),
                                       partial(jnp.sum, dtype=jnp.int32)))
        return "cnt", mk

    size = count_term(None)
    plan = []  # per spec: (op, count term or None, value term or None)
    for c, op in zip(vn, specs):
        d, v = tree[c]
        if op == "size":
            plan.append((op, size, None))
            continue
        mk = None
        if v is not None or jnp.issubdtype(d.dtype, jnp.floating):
            mk = c
            masks.setdefault(c, K.value_ok(d, v, padmask))
        if op == "count":
            plan.append((op, count_term(mk), None))
            continue
        if op in ("min", "max"):
            if jnp.issubdtype(d.dtype, jnp.floating):
                ident = np.inf if op == "min" else -np.inf
            elif d.dtype == jnp.bool_:
                ident = op == "min"
            else:
                info = jnp.iinfo(d.dtype)
                ident = info.max if op == "min" else info.min
            term = (op, c)
            terms.setdefault(term, (mk, d, jnp.array(ident, d.dtype),
                                    jnp.min if op == "min" else jnp.max))
        else:
            rdt = result_dtype(op, d.dtype)
            term = ("sum", c, rdt)
            terms.setdefault(term, (mk, d.astype(rdt), 0, jnp.sum))
        # pandas: sum over an all-null group is 0, and needs no count
        plan.append((op, None if op in ("sum", "sum64") else count_term(mk),
                     term))

    # the slot of every row that takes part, per distinct mask; -1 is
    # no slot
    part = {mk: jnp.where(m, slot, np.int32(-1)) for mk, m in masks.items()}

    def one_slot(g):
        return tuple(red(jnp.where(part[mk] == g, v, ident))
                     for mk, v, ident, red in terms.values())

    res = dict(zip(terms, jax.vmap(one_slot)(
        jnp.arange(n_slots, dtype=jnp.int32))))
    outs = []
    for op, cnt_term, term in plan:
        cnt = None if cnt_term is None else res[cnt_term].astype(jnp.int64)
        if term is None:  # count, size
            outs.append((cnt, None))
        elif cnt is None:  # sum, sum64
            outs.append((res[term], None))
        elif op == "mean":
            m = res[term] / jnp.maximum(cnt, 1)
            outs.append((jnp.where(cnt > 0, m, jnp.nan), None))
        else:  # SQL: SUM / MIN / MAX over an all-null group is NULL
            outs.append((res[term], cnt > 0))
    return res[size] > 0, outs


def _groupby_agg_dense(t: Table, keys, aggs, ranges) -> Table:
    """Sort-free dense groupby for small key spaces.

    When every key has a host-known range whose exact product K fits the
    slot budget, every row gets one of K dense slots (mixed-radix slot
    id) and `dense_agg_tail` aggregates per slot — no lax.sort at all:
    masked reductions over the rows for a handful of slots, `segment_*`
    scatters for many, the MXU one-hot matmul for float32 columns with
    Pallas on (`dense_route`; the span carries which). Group keys are
    reconstructed from the slot index and compacted
    ascending (slot order == lexicographic key order). This is the
    reference's one-pass hash groupby specialized to a perfect hash
    (reference: bodo/libs/groupby/_groupby.cpp hash-table path; SURVEY §7
    'dense segment_sum when packed key space is small')."""
    sizes = tuple(int(hi) - int(lo) + 1 for lo, hi in ranges)
    los = tuple(int(lo) for lo, _ in ranges)
    n_slots = 1
    for s in sizes:
        n_slots *= s
    specs = tuple(op for _, op, _ in aggs)
    val_names = tuple(c for c, _, _ in aggs)
    names = list(keys) + [c for c in val_names if c not in keys]
    tsel = t.select(list(dict.fromkeys(names)))
    # MXU one-hot matmul accumulate (TPU): sums/counts/means into a small
    # slot space go through the systolic array instead of scatter-adds
    from bodo_tpu.ops import pallas_kernels as PK
    use_mxu = ((PK.use_pallas() or PK.FORCE_INTERPRET)
               and n_slots <= PK.MAX_MATMUL_SLOTS
               and dense_mxu_ok(t.capacity,
                                [t.column(c).data.dtype for c in val_names],
                                specs))
    route = dense_route(n_slots, specs, use_mxu)
    tracing.annotate(dense_route=route)
    key = ("gbdense", _sig(tsel), tuple(keys), tuple(zip(val_names, specs)),
           sizes, los, route)
    fn = _jit_cache.get(key)
    if fn is None:
        kn, vn = list(keys), list(val_names)

        def body(tree, count):
            cap = tree[kn[0]][0].shape[0]
            return dense_agg_tail(tree, K.row_mask(count, cap), kn, vn,
                                  specs, sizes, los, n_slots, use_mxu)

        fn = named_jit("groupby_dense", body)
        _jit_cache[key] = fn

    with groupby_route("dense", len(keys), t.nrows, n_slots,
                       dense_route=route):
        out_keys, out_vals, ng = fn(tsel.device_data(),
                                    jnp.asarray(t.nrows))
        nrows = int(jax.device_get(ng))
    cols: Dict[str, Column] = {}
    for kname, kd in zip(keys, out_keys):
        src = t.column(kname)
        if src.dtype is dt.STRING:
            kd = kd.astype(np.int32)
        elif src.dtype.kind == "b":
            kd = kd.astype(bool)
        elif kd.dtype != src.dtype.numpy:
            kd = kd.astype(src.dtype.numpy)
        cols[kname] = Column(kd, None, src.dtype, src.dictionary, src.vrange)
    for (cname, op, oname), (vd, vv) in zip(aggs, out_vals):
        src = t.column(cname)
        cols[oname] = _agg_out_col(src, op, vd, vv)
    return shrink_to_fit(Table(cols, nrows, REP, None))


def _groupby_agg_colocated(t: Table, keys, aggs) -> Table:
    """Distributed groupby for non-decomposable aggs (nunique, quantile,
    median): one hash shuffle co-locates every group on a single shard,
    then each shard finishes its groups with the full local kernel — the
    reference's shuffle-then-update strategy for nunique/median
    (bodo/libs/groupby/_groupby.cpp shuffle path)."""
    t = shrink_to_fit(shuffle_by_key(t, keys))
    specs = tuple(op for _, op, _ in aggs)
    val_names = [c for c, _, _ in aggs]
    m = mesh_mod.get_mesh()
    key = ("gbcoloc", _mesh_key(m), _sig(t), tuple(keys), tuple(specs),
           tuple(val_names))
    fn = _jit_cache.get(key)
    if fn is None:
        kn = list(keys)
        ax = config.data_axis

        def sharded(tree, counts):
            cap = tree[kn[0]][0].shape[0]
            arrays = tuple(tree[k] for k in kn) + \
                tuple(tree[c] for c in val_names)
            pk, pv, ng = groupby_local(arrays, counts[0], specs, cap,
                                       len(kn))
            return (pk, pv), ng[None]

        fn = named_jit("groupby_colocated", C.smap(
            sharded, in_specs=(P(ax), P(ax)), out_specs=(P(ax), P(ax)),
            mesh=m))
        _jit_cache[key] = fn

    (out_keys, out_vals), ngs = fn(t.device_data(), t.counts_device())
    counts = np.asarray(jax.device_get(ngs)).reshape(-1).astype(np.int64)
    cols: Dict[str, Column] = {}
    for kname, (kd, kv) in zip(keys, out_keys):
        src = t.column(kname)
        cols[kname] = Column(kd, kv, src.dtype, src.dictionary, src.vrange)
    for (cname, op, oname), (vd, vv) in zip(aggs, out_vals):
        src = t.column(cname)
        cols[oname] = _agg_out_col(src, op, vd, vv)
    return shrink_to_fit(Table(cols, int(counts.sum()), ONED, counts))


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

@_traced
@_governed("sort_table")
def sort_table(t: Table, by: Sequence[str], ascending=None,
               na_last: bool = True) -> Table:
    _inject_collective(t, op="sort_table")
    by = list(by)
    local = _as_local(t)
    if local is not None:
        return sort_table(local, by, ascending, na_last)
    if ascending is None:
        ascending = [True] * len(by)
    elif isinstance(ascending, bool):
        ascending = [ascending] * len(by)
    # packed path: all-ascending small-range keys sort by one int64
    if all(ascending) and na_last and len(by) > 1:
        pack = _pack_plan(t, by, 62)
        if pack is not None:
            tp = _packed_key_table(t, pack, with_valid=False)
            res = sort_table(tp, ["__packed"], [True], na_last)
            return _keep_vranges(res.select(t.names), t)
    others = [n for n in t.names if n not in by]
    order = by + others
    arrays = tuple((t.column(n).data, t.column(n).valid) for n in order)

    if t.distribution == ONED:
        t = shrink_to_fit(t)
        arrays = tuple((t.column(n).data, t.column(n).valid) for n in order)
        out, cnts = sort_sharded(arrays, t.counts_device(), len(by),
                                 tuple(ascending), na_last)
        counts = np.asarray(jax.device_get(cnts)).reshape(-1).astype(np.int64)
        res_tree = {n: out[i] for i, n in enumerate(order)}
        res = shrink_to_fit(t.with_device_data(
            res_tree, nrows=int(counts.sum()), counts=counts))
    else:
        out, _ = sort_local(arrays, jnp.asarray(t.nrows), len(by),
                            tuple(ascending), na_last)
        res_tree = {n: out[i] for i, n in enumerate(order)}
        res = t.with_device_data(res_tree, nrows=t.nrows)
    return _keep_vranges(res.select(t.names), t)


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def _suffix_columns(left: Table, right: Table, left_on, right_on,
                    suffixes) -> Tuple[Dict[str, str], Dict[str, str]]:
    overlap = (set(left.names) & set(right.names)) - \
        (set(left_on) & set(right_on))
    lmap = {n: (n + suffixes[0] if n in overlap else n) for n in left.names}
    rmap = {n: (n + suffixes[1] if n in overlap else n) for n in right.names
            if not (n in right_on and left_on[right_on.index(n)] == n)}
    return lmap, rmap


@_traced
@_governed("join_tables")
def join_tables(left: Table, right: Table, left_on: Sequence[str],
                right_on: Sequence[str], how: str = "inner",
                suffixes=("_x", "_y"), null_equal: bool = True) -> Table:
    """Join (pandas merge analogue).
    how: inner / left / right / outer / cross (reference join matrix:
    bodo/libs/_hash_join.cpp build_table_outer/probe_table_outer,
    _nested_loop_join_impl.cpp for cross). null_equal=True gives pandas
    merge semantics (NaN keys match each other); SQL passes False (null
    keys never match, the reference's is_na_equal=false join mode).

    Build side: the right, probed by the left, on every route but one.
    Two replicated tables first try the LUT routes on the right
    (`_join_lut_try`: dense, then hash; both need unique build keys). A
    right side that is no LUT, because `keys_must_repeat` says so before
    anything is built or because its builds found a key twice, sends an
    inner join with a left side of no more rows to the same two routes
    with the sides exchanged: the LUT is built on the left and probed by
    the right (`build="left"` on the route span, `join_build_left` in
    `fusion.stats()`), and the columns come back left-then-right under
    the suffixes asked for. Only when the left repeats too
    (many-to-many) does the join sort (`_join_rep`). The rows of an
    inner join come in the probe side's order, whichever side that was:
    no order is promised (`reorder_joins` permutes it as well)."""
    _inject_collective(left, right, op="join_tables")
    left_on, right_on = list(left_on), list(right_on)
    assert how in ("inner", "left", "right", "outer", "cross"), \
        f"join how={how} not supported"
    if how == "cross":
        return _cross_join(left, right, suffixes)
    if how == "right":
        # right join = left join with sides swapped; restore the pandas
        # column order (left's columns first) afterwards
        out = join_tables(right, left, right_on, left_on, "left",
                          (suffixes[1], suffixes[0]), null_equal)
        lmap, rmap = _suffix_columns(left, right, left_on, right_on,
                                     suffixes)
        names = [lmap[n] for n in left.names if lmap[n] in out.columns]
        names += [rmap[n] for n in right.names
                  if n in rmap and rmap[n] in out.columns]
        return out.select(list(dict.fromkeys(names)))

    # unify dictionaries of string join keys so codes are comparable, and
    # align numeric key dtypes so hashing/comparison agree across sides
    left = left.with_columns(left.columns)
    right = right.with_columns(right.columns)
    for lk, rk in zip(left_on, right_on):
        lc, rc = left.columns[lk], right.columns[rk]
        if lc.dtype is dt.STRING or rc.dtype is dt.STRING:
            _, (nl, nr) = unify_dictionaries([lc, rc])
            left.columns[lk] = nl
            right.columns[rk] = nr
        elif lc.dtype is not rc.dtype and dt.is_numeric(lc.dtype) and \
                dt.is_numeric(rc.dtype):
            common = dt.common_numeric(lc.dtype, rc.dtype)
            # Refuse lossy key casts: promoting 64-bit integer keys to
            # float64 collapses distinct keys above 2^53 into equal ones,
            # silently producing wrong join results.
            for side in (lc, rc):
                if (np.dtype(side.dtype.numpy).kind in "iu"
                        and np.dtype(side.dtype.numpy).itemsize == 8
                        and np.dtype(common.numpy).kind == "f"):
                    raise NotImplementedError(
                        f"join on {lc.dtype.name} vs {rc.dtype.name} keys "
                        f"would promote a 64-bit integer key to float64, "
                        f"which is lossy above 2**53; cast one side "
                        f"explicitly to a common exact type first")
            if lc.dtype is not common:
                left.columns[lk] = Column(lc.data.astype(common.numpy),
                                          lc.valid, common, None)
            if rc.dtype is not common:
                right.columns[rk] = Column(rc.data.astype(common.numpy),
                                           rc.valid, common, None)

    ll, rl = _as_local(left), _as_local(right)
    if ll is not None:
        left = ll
    if rl is not None:
        right = rl
    from bodo_tpu.plan import adaptive
    if left.distribution == REP and right.distribution == ONED:
        if how == "inner" and \
                adaptive.join_broadcast_decision(left, right):
            # the mirror case below with its answer known: a small
            # replicated left under a sharded right is the build side as
            # it stands, so it is not scattered to be gathered again
            out = join_tables(right, left, right_on, left_on, "inner",
                              (suffixes[1], suffixes[0]), null_equal)
            return _left_then_right(out, left, right, left_on, right_on,
                                    suffixes)
        left = left.shard()
    if left.distribution == REP and right.distribution == REP:
        out = _join_lut_try(left, right, left_on, right_on, how, suffixes,
                            null_equal)
        if out is not None:
            return out
        if how == "inner" and left_on and left.nrows <= right.nrows:
            # the right side is no LUT; an inner join is symmetric, so
            # the smaller left may be one, probed by the right
            out = _join_lut_try(right, left, right_on, left_on, how,
                                (suffixes[1], suffixes[0]), null_equal,
                                build_left=True)
            if out is not None:
                return _left_then_right(out, left, right, left_on,
                                        right_on, suffixes)
    if how == "outer" and left.distribution == ONED and \
            right.distribution == REP:
        # a replicated build side would emit its unmatched rows once PER
        # SHARD; shard it so every build row is owned by exactly one shard
        right = right.shard()
    if left.distribution == ONED and right.distribution == REP:
        if adaptive.should_demote_broadcast(right):
            # AQE demotion: the planned broadcast's observed build side
            # blows the governor budget — shard it and shuffle instead
            right = right.shard()
        else:
            # a build side that is replicated as it came: nothing to
            # move on the host, the span only says which way
            with exchange("broadcast", rows=right.nrows):
                pass
    if how != "outer" and \
            left.distribution == ONED and right.distribution == ONED and \
            adaptive.join_broadcast_decision(right, left):
        # runtime broadcast decision on ACTUAL sizes (not scan-time
        # heuristics): replicating a small build side skips shuffling the
        # big probe side entirely (reference: broadcast join sizing,
        # bodo/libs/_shuffle.h:153); with AQE on the gate is the build's
        # observed bytes against the governor's derived budget
        with exchange("broadcast", rows=right.nrows):
            right = right.gather()
    elif how == "inner" and left.distribution == ONED and \
            right.distribution == ONED and \
            adaptive.join_broadcast_decision(left, right):
        # mirror case: tiny LEFT side — swap (inner join is symmetric),
        # broadcast it, and restore the left-then-right column order
        out = join_tables(right, left, right_on, left_on, "inner",
                          (suffixes[1], suffixes[0]), null_equal)
        return _left_then_right(out, left, right, left_on, right_on,
                                suffixes)
    if left.distribution == ONED and right.distribution == ONED:
        out = adaptive.try_skew_split_join(left, right, left_on, right_on,
                                           how, suffixes, null_equal)
        if out is not None:
            return out
    with join_route("sort", len(left_on), left.nrows, right.nrows):
        if left.distribution == ONED and right.distribution == ONED:
            return _join_sharded(left, right, left_on, right_on, how,
                                 suffixes, null_equal=null_equal)
        if left.distribution == ONED and right.distribution == REP:
            return _join_broadcast(left, right, left_on, right_on, how,
                                   suffixes, null_equal)
        return _join_rep(left, right, left_on, right_on, how, suffixes,
                         null_equal)


def _left_then_right(out: Table, left, right, left_on, right_on,
                     suffixes) -> Table:
    """The result of an inner join run with its sides exchanged (and its
    suffixes with them), in the column order of the join asked for:
    left's columns, then right's."""
    lmap, rmap = _suffix_columns(left, right, left_on, right_on, suffixes)
    names = [lmap[n] for n in left.names] + \
        [rmap[n] for n in right.names if n in rmap]
    return out.select([n for n in names if n in out.columns])


def _host_span(c: Column) -> Optional[int]:
    """How many values a column can take by what the host knows of it
    without touching the device, as `_key_ranges` reads it: a string's
    dictionary size, a bool's two, the span of an integer's or a date's
    `vrange`. None without one."""
    if c.dtype is dt.STRING:
        return None if c.dictionary is None else max(len(c.dictionary), 1)
    if c.dtype.kind == "b":
        return 2
    if c.vrange is not None and (c.dtype.kind in ("i", "u")
                                 or c.dtype is dt.DATE):
        return int(c.vrange[1]) - int(c.vrange[0]) + 1
    return None


def keys_must_repeat(t: Table, keys: Sequence[str],
                     bounds: Optional[Sequence[Optional[int]]] = None
                     ) -> bool:
    """Whether `t` must hold some combination of `keys` twice, decided
    on the host: every key column has a bound on its distinct values,
    none carries a `valid` mask (null keys never claim a slot, so they
    prove nothing), and `t` has more rows than the product of the
    bounds (the pigeonhole principle). `bounds[i]`, where given, is the
    caller's bound on `keys[i]` (a range it has just reduced,
    `plan/stats.key_ndv_bound`); `_host_span` serves the rest. Such a
    side is no LUT: the dense build and the hash build would each run,
    sync and refuse, so `_join_dense_try` and
    `fusion_join._run_join_group` ask here first. A bound need not be
    tight and a filter below only lowers `nrows`, so an answer of True
    sends a join only where its builds would have sent it anyway."""
    if not keys:
        return False
    combos = 1
    for i, k in enumerate(keys):
        c = t.column(k)
        if c.valid is not None:
            return False
        b = bounds[i] if bounds is not None else None
        if b is None:
            b = _host_span(c)
        if b is None:
            return False
        combos *= max(int(b), 1)
        if combos >= t.nrows:
            return False
    return True


# `_join_dense_try`'s answer for a build side whose keys must repeat:
# not a dense LUT and not a hash LUT either (`_join_lut_try`)
_KEYS_REPEAT = object()


def _join_lut_try(left, right, left_on, right_on, how, suffixes,
                  null_equal: bool = True,
                  build_left: bool = False) -> Optional[Table]:
    """The two LUT routes of a replicated equi-join with the LUT built
    on `right` and probed by `left`: dense, then hash. None when `right`
    is no LUT (its keys repeat, or neither route applies).
    `build_left` says the caller exchanged the join's sides (`right`
    here is the join's left); it rides to the route span."""
    out = _join_dense_try(left, right, left_on, right_on, how, suffixes,
                          null_equal, build_left)
    if out is _KEYS_REPEAT:
        return None  # the hash build would find the same duplicates
    if out is None and left_on:
        out = _join_hash_try(left, right, left_on, right_on, how, suffixes,
                             null_equal, build_left)
    return out


def _lut_route(route: str, nk: int, probe: Table, build: Table,
               build_left: bool):
    """`join_route` for a LUT join, in the join's own left and right."""
    left, right = (build, probe) if build_left else (probe, build)
    return join_route(route, nk, left.nrows, right.nrows, build_left)


# Dense-LUT join: build sides whose key-range product is at most this
# many slots (and whose keys are unique) join by perfect-hash gather.
DENSE_JOIN_MAX_SLOTS = 1 << 22


def _join_dense_try(left, right, left_on, right_on, how, suffixes,
                    null_equal: bool = True, build_left: bool = False):
    """Dense-LUT equi-join: when the build (right) side's keys have a
    small host-known range and are unique, the join is a perfect-hash
    lookup — build scatters row indices into a dense LUT, probe gathers.
    No sort, no shuffle; unique build keys ⇒ ≤1 match per probe row, so
    a left join's output is the probe table with the build columns
    gathered beside it (one program), and an inner join's probe returns
    only its `hit` mask, the build row index and the count: the host
    reads the count and `_join_emit` gathers the columns at the size of
    the result. The dimension-table fast path of the
    reference's hash join (bodo/libs/_hash_join.cpp build/probe) mapped
    onto gather/scatter. Returns None when not applicable (the caller
    tries the hash LUT, then the union-segmentation sort join), and
    `_KEYS_REPEAT` when the ranges reduced for the gate show, before
    anything is built, that the build side has more rows than its keys
    have values (`keys_must_repeat`): no LUT of either kind."""
    if how not in ("inner", "left") or right.nrows == 0:
        return None
    if null_equal and \
            any(left.column(k).valid is not None for k in left_on) and \
            any(right.column(k).valid is not None for k in right_on):
        # dense slots drop null keys (SQL style); under pandas null-match
        # semantics a null-null pair would be silently missed when both
        # sides can hold nulls — use the sort join there
        return None
    ranges, inexact = _key_ranges(right, right_on)
    if any(r is None for r in ranges):
        return None
    if keys_must_repeat(right, right_on,
                        [int(hi) - int(lo) + 1 for lo, hi in ranges]):
        join_build_skipped()
        return _KEYS_REPEAT

    def _slots(rs) -> int:
        n = 1
        for lo, hi in rs:
            n *= int(hi) - int(lo) + 1
            if n > DENSE_JOIN_MAX_SLOTS:
                break
        return n

    n_slots = _slots(ranges)
    ok = (n_slots <= DENSE_JOIN_MAX_SLOTS and
          n_slots <= 16 * right.nrows + 1024)
    if not ok and inexact:
        ranges, inexact = _refine_ranges(right, right_on, ranges, inexact)
        n_slots = _slots(ranges)
        ok = (n_slots <= DENSE_JOIN_MAX_SLOTS and
              n_slots <= 16 * right.nrows + 1024)
    if not ok:
        return None  # too large or too sparse: LUT cost would dominate
    sizes = tuple(int(hi) - int(lo) + 1 for lo, hi in ranges)
    los = tuple(int(lo) for lo, _ in ranges)

    lorder, rorder, pa, ba = _probe_build_arrays(left, right, left_on,
                                                 right_on)
    nk = len(left_on)

    bkey = ("densejoin_build", _sig(right.select(rorder)), sizes, los, nk)
    bfn = _jit_cache.get(bkey)
    if bfn is None:
        def bbody(arrays, count):
            cap = arrays[0][0].shape[0]
            slot, mask = _dense_slots(arrays[:nk], los, sizes,
                                      K.row_mask(count, cap))
            cnt = jax.ops.segment_sum(mask.astype(jnp.int32),
                                      slot, num_segments=n_slots)
            dup = jnp.any(cnt > 1)
            idx_scatter = jnp.where(mask, slot, n_slots)
            lut = jnp.full((n_slots,), -1, dtype=jnp.int32)
            lut = lut.at[idx_scatter].set(
                jnp.arange(cap, dtype=jnp.int32), mode="drop")
            return lut, dup

        bfn = named_jit("join_build_dense", bbody)
        _jit_cache[bkey] = bfn

    lut, dup = bfn(ba, jnp.asarray(right.nrows))
    if bool(jax.device_get(dup)):
        return None  # duplicate build keys: not a perfect hash

    # the LUT gather is the probe's hot lookup: small LUTs route through
    # the Pallas one-hot MXU gather (values are row indices, bounded by
    # MAX_GATHER_VALUE so the f32 contraction is exact)
    from bodo_tpu.ops import pallas_kernels as PK
    use_gather = ((PK.use_pallas() or PK.FORCE_INTERPRET)
                  and n_slots <= PK.MAX_MATMUL_SLOTS
                  and right.capacity < PK.MAX_GATHER_VALUE)
    inner = how == "inner"
    # an inner probe is handed the key columns alone, and no build array
    pkey = ("densejoin_probe",
            _sig(left.select(lorder[:nk] if inner else lorder)),
            None if inner else _sig(right.select(rorder)),
            sizes, los, nk, how, use_gather)
    pfn = _jit_cache.get(pkey)
    if pfn is None:
        def pbody(p_arrays, b_arrays, lut, pcount):
            cap = p_arrays[0][0].shape[0]
            slot, live = _dense_slots(p_arrays[:nk], los, sizes,
                                      K.row_mask(pcount, cap),
                                      strict_range=True)
            g = PK.matmul_gather(slot, lut) if use_gather else lut[slot]
            idx = jnp.where(live, g, -1)
            hit = idx >= 0
            safe = jnp.maximum(idx, 0)
            if inner:
                # touches no column: `_join_emit` gathers them at the
                # size of the result once the host has read `cnt`
                return hit, safe, jnp.sum(hit)
            # left join: keep every probe row; unmatched build cols invalid
            return tuple(p_arrays), _gather_build(b_arrays, hit, safe), pcount

        pfn = named_jit("join_probe_dense", pbody)
        _jit_cache[pkey] = pfn

    with _lut_route("dense", nk, left, right, build_left):
        pc = jnp.asarray(left.nrows)
        if inner:
            hit, safe, cnt = pfn(pa[:nk], (), lut, pc)
            return _join_emit(left, right, left_on, right_on, suffixes,
                              (lorder, rorder, pa, ba), hit, safe,
                              int(jax.device_get(cnt)))
        out_p, out_b, cnt = pfn(pa, ba, lut, pc)
        nrows = int(jax.device_get(cnt))
        res = _assemble_join(left, right, left_on, right_on, lorder, rorder,
                             out_p, out_b, nrows, None, how, suffixes)
        return rebucket(res)


def _join_hash_try(left, right, left_on, right_on, how, suffixes,
                   null_equal: bool = True,
                   build_left: bool = False) -> Optional[Table]:
    """Hash-LUT equi-join: the dense-LUT fast path freed from its
    key-range gate. The build side claims slots in a scatter-claim hash
    table (ops/hashtable.py) — owner IS the LUT — and probe rows follow
    the same double-hash sequence to a match or an empty slot. Unique
    build keys ⇒ ≤1 match per probe row, no sort, no shuffle; the output
    is emitted as the dense LUT's is (a left join in the probe program,
    an inner join by `_join_emit` at the size of its result). Arbitrary
    key dtypes/ranges
    (reference: bodo/libs/_hash_join.cpp build/probe). Returns None on
    duplicate build keys or probe-round exhaustion (caller falls back
    to the sort join)."""
    from bodo_tpu.ops import hashtable as HT
    if how not in ("inner", "left") or right.nrows == 0 or \
            not config.hash_join:
        return None
    lorder, rorder, pa, ba = _probe_build_arrays(left, right, left_on,
                                                 right_on)
    nk = len(left_on)
    T = HT.table_size(right.capacity)
    # probe-independent null-column layout: an all-True layout is always
    # legal (encode_columns_aligned zero-fills the null code column when
    # a side can't produce nulls), and making the layout independent of
    # the probe side lets this per-node path share the device-resident
    # build cache with fused join groups and streaming probes
    null_cols = (True,) * nk

    if config.fusion_join:
        from bodo_tpu.plan import fusion_join
        built = fusion_join.build_hash_table(right, right_on, null_cols,
                                             null_equal)
        if built is None:
            return None  # duplicate build keys (or pathological probing)
        bcodes, owner = built
    else:
        bkey = ("hashjoin_build", _sig(right.select(rorder)), nk,
                null_equal, T, null_cols)
        bfn = _jit_cache.get(bkey)
        if bfn is None:
            def bbody(arrays, count):
                cap = arrays[0][0].shape[0]
                codes, null_ok = HT.encode_columns_aligned(
                    arrays[:nk], null_cols, null_equal)
                ok = K.row_mask(count, cap)
                if null_ok is not None:
                    ok = ok & null_ok
                slot, owner, _r, unresolved = HT.claim_slots(codes, ok, T)
                cnt = jnp.zeros(T, jnp.int32).at[
                    jnp.where(slot >= 0, slot, T)].add(1, mode="drop")
                dup = jnp.any(cnt > 1)
                return codes, owner, dup | unresolved

            bfn = named_jit("join_build_hash", bbody)
            _jit_cache[bkey] = bfn

        bcodes, owner, bad = bfn(ba, jnp.asarray(right.nrows))
        if bool(jax.device_get(bad)):
            return None  # duplicate build keys (or pathological probing)

    inner = how == "inner"
    pkey = ("hashjoin_probe",
            _sig(left.select(lorder[:nk] if inner else lorder)),
            None if inner else _sig(right.select(rorder)),
            nk, null_equal, T, how, null_cols)
    pfn = _jit_cache.get(pkey)
    if pfn is None:
        def pbody(p_arrays, b_arrays, bcodes, owner, pcount):
            cap = p_arrays[0][0].shape[0]
            codes, null_ok = HT.encode_columns_aligned(
                p_arrays[:nk], null_cols, null_equal)
            live = K.row_mask(pcount, cap)
            if null_ok is not None:
                live = live & null_ok
            idx, p_unres = HT.probe_slots(bcodes, owner, codes, live, T)
            hit = idx >= 0
            safe = jnp.maximum(idx, 0)
            if inner:
                # as the dense probe: no column, `_join_emit` has them
                return hit, safe, jnp.sum(hit), p_unres
            return (tuple(p_arrays), _gather_build(b_arrays, hit, safe),
                    pcount, p_unres)

        pfn = named_jit("join_probe_hash", pbody)
        _jit_cache[pkey] = pfn

    with _lut_route("hash", nk, left, right, build_left):
        pc = jnp.asarray(left.nrows)
        if inner:
            hit, safe, cnt, p_unres = pfn(pa[:nk], (), bcodes, owner, pc)
        else:
            out_p, out_b, cnt, p_unres = pfn(pa, ba, bcodes, owner, pc)
        nrows_, unres_ = jax.device_get((cnt, p_unres))
        if bool(unres_):
            return None  # pathological probe chains: the sort join's
        if inner:
            return _join_emit(left, right, left_on, right_on, suffixes,
                              (lorder, rorder, pa, ba), hit, safe,
                              int(nrows_))
        res = _assemble_join(left, right, left_on, right_on, lorder, rorder,
                             out_p, out_b, int(nrows_), None, how, suffixes)
        return rebucket(res)


def _gather_build(b_arrays, hit, safe):
    """A LUT probe's build columns at every probe row: row `safe` of
    each, valid where the probe row `hit` (and the build value is)."""
    return tuple((d[safe], hit if v is None else (hit & v[safe]))
                 for d, v in b_arrays)


def _join_emit(left, right, left_on, right_on, suffixes, arrays, hit, safe,
               nrows: int) -> Table:
    """An inner LUT join's result from its probe's `hit` mask and build
    row index `safe`, at the size of the result (`nrows`, which the
    host has just read; `arrays` is `_probe_build_arrays`'). Every live
    probe row hit: they are already a prefix, nothing is compacted, and
    the build columns are gathered at `safe`. Otherwise one program at
    the capacity `rebucket` would leave the result (`rebucket_capacity`:
    the probe table's while the hits fill `REBUCKET_THRESHOLD` of it, so
    that a join keeping most of its rows has one shape whatever the
    count; `round_capacity(nrows)` below it): the hits' source index
    once (`K.compact_index`), probe columns gathered at it, build
    columns at `safe` of it, so no column is scattered and none is
    touched at the probe table's capacity when the result is small."""
    lorder, rorder, pa, ba = arrays
    skip = nrows == left.nrows
    join_emitted(nrows, skip)
    out_cap = None if skip else rebucket_capacity(nrows, left.capacity)
    ekey = ("join_probe_emit", _sig(left.select(lorder)),
            _sig(right.select(rorder)), out_cap)
    efn = _jit_cache.get(ekey)
    if efn is None:
        def ebody(p_arrays, b_arrays, hit, safe):
            if out_cap is None:
                return (), _gather_build(b_arrays, hit, safe)
            src, n = K.compact_index(hit, out_cap)

            def take(idx, pairs):
                # `None` valids pass through `take_compacted`
                return tuple(zip(
                    K.take_compacted(idx, n, [d for d, _ in pairs]),
                    K.take_compacted(idx, n, [v for _, v in pairs])))
            keep = K.row_mask(n, out_cap)
            return take(src, p_arrays), tuple(
                (d, keep if v is None else v)
                for d, v in take(safe[src], b_arrays))

        efn = named_jit("join_probe_emit", ebody)
        _jit_cache[ekey] = efn

    out_p, out_b = efn(() if skip else pa, ba, hit, safe)
    res = _assemble_join(left, right, left_on, right_on, lorder, rorder,
                         pa if skip else out_p, out_b, nrows, None, "inner",
                         suffixes)
    # a compacted result is born at its capacity (a no-op here); an
    # all-hit one keeps the probe table's, which may be far above its rows
    return rebucket(res)


def _probe_build_arrays(left, right, left_on, right_on):
    lorder = left_on + [n for n in left.names if n not in left_on]
    rorder = right_on + [n for n in right.names if n not in right_on]
    pa = tuple((left.column(n).data, left.column(n).valid) for n in lorder)
    ba = tuple((right.column(n).data, right.column(n).valid) for n in rorder)
    return lorder, rorder, pa, ba


def _assemble_join(left, right, left_on, right_on, lorder, rorder,
                   out_p, out_b, nrows, counts, how, suffixes) -> Table:
    lmap, rmap = _suffix_columns(left, right, left_on, right_on, suffixes)
    cols: Dict[str, Column] = {}
    # full outer with a merged key column (same name both sides): pandas
    # fills the key from the right side on build-only appended rows
    merged_keys = {}
    if how == "outer":
        for i, (ln, rn) in enumerate(zip(left_on, right_on)):
            if ln == rn:
                merged_keys[ln] = i
    for i, n in enumerate(lorder):
        src = left.column(n)
        d, v = out_p[i]
        vr = src.vrange
        if n in merged_keys:
            ki = merged_keys[n]
            bd, bv = out_b[ki]
            assert v is not None and bv is not None
            d = jnp.where(v, d, bd.astype(d.dtype))
            v = v | bv
            # the merged column now carries RIGHT-side values on
            # build-only rows, so the left bound alone is unsound: a
            # later dense groupby/join would trust a stale (lo, hi) and
            # silently mis-slot right-only keys. Union both bounds
            # (None if either side is unbounded).
            rvr = right.column(right_on[ki]).vrange
            if vr is not None and rvr is not None:
                tight = (len(vr) > 2 and vr[2]) and (len(rvr) > 2
                                                     and rvr[2])
                vr = (min(vr[0], rvr[0]), max(vr[1], rvr[1]), tight)
            else:
                vr = None
        cols[lmap[n]] = Column(d, v, src.dtype, src.dictionary, vr)
    for i, n in enumerate(rorder):
        if n not in rmap:
            continue
        src = right.column(n)
        d, v = out_b[i]
        cols[rmap[n]] = Column(d, v, src.dtype, src.dictionary, src.vrange)
    dist = ONED if counts is not None else REP
    res = Table(cols, nrows, dist, counts)
    # restore pandas-ish column order: left cols then right cols
    names = [lmap[n] for n in left.names] + \
        [rmap[n] for n in right.names if n in rmap]
    return res.select(names)


def _join_rep(left, right, left_on, right_on, how, suffixes,
              null_equal: bool = True) -> Table:
    lorder, rorder, pa, ba = _probe_build_arrays(left, right, left_on,
                                                 right_on)
    pc = jnp.asarray(left.nrows)
    bc = jnp.asarray(right.nrows)
    nk = len(left_on)
    out_cap = round_capacity(max(left.nrows, right.nrows, 1))
    method = "hash" if config.hash_join else "sort"
    for _ in range(4):
        out_p, out_b, cnt, ovf, unres = join_local(
            pa, ba, pc, bc, nk, how, out_cap, null_equal, method)
        if method == "hash" and bool(jax.device_get(unres)):
            method = "sort"  # pathological probe chains: sort safety net
            continue
        if not bool(jax.device_get(ovf)):
            break
        total, _ = join_count(pa[:nk], ba[:nk], pc, bc, nk, how,
                              null_equal, method)
        out_cap = round_capacity(int(jax.device_get(total)))
    nrows = int(jax.device_get(cnt))
    return _assemble_join(left, right, left_on, right_on, lorder, rorder,
                          out_p, out_b, nrows, None, how, suffixes)


def _flatten_with_valids(arrays):
    flat, slots = [], []
    for d, v in arrays:
        flat.append(d)
        if v is not None:
            slots.append(True)
            flat.append(v)
        else:
            slots.append(False)
    return flat, slots


def _rebuild_from_flat(flat, slots):
    out, j = [], 0
    for has_v in slots:
        if has_v:
            out.append((flat[j], flat[j + 1].astype(bool)))
            j += 2
        else:
            out.append((flat[j], None))
            j += 1
    return tuple(out)


def _build_join_sharded_fn(mesh_key, nk, how, out_cap, broadcast: bool,
                           sig_key, null_equal: bool = True,
                           method: str = "sort"):
    """shard_map join of co-located shards — probe rows and build rows
    with equal keys are already on the same shard (hash shuffle happened
    as a separate sized stage via shuffle_by_key), or the build side is
    replicated (broadcast join, reference bodo/libs/_shuffle.h:153).
    Analogue of the reference's partitioned hash join
    (streaming/_join.h:892); with method='hash' the per-shard kernel is
    the scatter-claim hash join rather than the sort join."""
    key = ("join", mesh_key, nk, how, out_cap, broadcast, sig_key,
           null_equal, method)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    mesh = _MESHES[mesh_key]
    ax = config.data_axis

    def body(p_arrays, b_arrays, pcounts, bcounts):
        out_p, out_b, cnt, ovf, unres = join_local(
            p_arrays, b_arrays, pcounts[0], bcounts[0], nk, how, out_cap,
            null_equal, method)
        return out_p, out_b, cnt[None], ovf[None], unres[None]

    fn = named_jit("join_sharded", C.smap(
        body,
        in_specs=(P(ax), P() if broadcast else P(ax),
                  P(ax), P() if broadcast else P(ax)),
        out_specs=(P(ax), P(ax), P(ax), P(ax), P(ax)),
        mesh=mesh))
    _jit_cache[key] = fn
    return fn


def _build_join_count_sharded_fn(mesh_key, nk, how, broadcast: bool,
                                 sig_key, null_equal: bool = True,
                                 method: str = "sort"):
    """Every shard's exact join output count (`join_count` over the key
    columns alone), for the retry of `_join_sharded` after an output
    bucket overflowed."""
    key = ("join_count", mesh_key, nk, how, sig_key, null_equal, method,
           broadcast)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    ax = config.data_axis

    def body(p_arrays, b_arrays, pcounts, bcounts):
        return join_count(p_arrays[:nk], b_arrays[:nk], pcounts[0],
                          bcounts[0], nk, how, null_equal,
                          method)[0][None]

    fn = named_jit("join_count_sharded", C.smap(
        body,
        in_specs=(P(ax), P() if broadcast else P(ax), P(ax),
                  P() if broadcast else P(ax)),
        out_specs=P(ax), mesh=_MESHES[mesh_key]))
    _jit_cache[key] = fn
    return fn


def _join_sharded(left, right, left_on, right_on, how, suffixes,
                  broadcast: bool = False,
                  null_equal: bool = True,
                  pre_shuffled: bool = False) -> Table:
    m = mesh_mod.get_mesh()
    if not broadcast and not pre_shuffled:
        # co-locate equal keys, then join at tight static shapes
        with exchange("shuffle", keys=len(left_on), rows_left=left.nrows,
                      rows_right=right.nrows):
            left = shuffle_by_key(left, left_on)
            right = shuffle_by_key(right, right_on)
    left = shrink_to_fit(left)
    lorder, rorder, pa, ba = _probe_build_arrays(left, right, left_on,
                                                 right_on)
    nk = len(left_on)
    pcap = left.shard_capacity
    # optimistic: ≈1 match per probe row (the FK-join common case); the
    # overflow flag grows the bucket, exact count caps the last retry
    out_cap = round_capacity(2 * pcap)
    if broadcast:
        bcounts = jnp.asarray([right.nrows], dtype=jnp.int64)
    else:
        bcounts = right.counts_device()
    sig_key = (_sig(left), _sig(right))
    method = "hash" if config.hash_join else "sort"
    for attempt in range(4):
        fn = _build_join_sharded_fn(_mesh_key(m), nk, how, out_cap,
                                    broadcast, sig_key, null_equal,
                                    method)
        out_p, out_b, cnts, ovf, unres = fn(pa, ba, left.counts_device(),
                                            bcounts)
        if (method == "hash"
                and np.asarray(jax.device_get(unres)).any()):
            method = "sort"  # pathological probe chains on some shard
            continue
        if not np.asarray(jax.device_get(ovf)).any():
            break
        # exact per-shard counts, then one final right-sized run
        cfn = _build_join_count_sharded_fn(_mesh_key(m), nk, how,
                                           broadcast, sig_key, null_equal,
                                           method)
        exact = np.asarray(jax.device_get(
            cfn(pa, ba, left.counts_device(), bcounts)))
        out_cap = round_capacity(int(exact.max()))
    else:
        raise RuntimeError("join output overflow after exact-count retry")
    counts = np.asarray(jax.device_get(cnts)).reshape(-1).astype(np.int64)
    res = _assemble_join(left, right, left_on, right_on, lorder, rorder,
                         out_p, out_b, int(counts.sum()), counts, how,
                         suffixes)
    return shrink_to_fit(res)


def _join_broadcast(left, right, left_on, right_on, how, suffixes,
                    null_equal: bool = True) -> Table:
    return _join_sharded(left, right, left_on, right_on, how, suffixes,
                         broadcast=True, null_equal=null_equal)


def _cross_join(left, right, suffixes) -> Table:
    """Cartesian product (merge how='cross'). Distributed form: left rows
    stay sharded, the right side is replicated, every shard emits its
    local probe-major block — output row order matches pandas because
    shard row ranges are ordered. Output size is known exactly on the
    host (nl x nr), so capacities are right-sized with no overflow retry."""
    from bodo_tpu.ops.join import cross_local

    ll, rl = _as_local(left), _as_local(right)
    if ll is not None:
        left = ll
    if rl is not None:
        right = rl
    if left.distribution == REP and right.distribution == ONED:
        # output order follows left rows; replicate the right side
        right = right.gather()
    if left.distribution == ONED:
        if right.distribution == ONED:
            right = right.gather()
        left = shrink_to_fit(left)
        lorder, rorder, pa, ba = _probe_build_arrays(left, right, [], [])
        m = mesh_mod.get_mesh()
        percap = int(max(left.counts)) if len(left.counts) else 0
        out_cap = round_capacity(max(percap * max(right.nrows, 1), 1))
        key = ("crossjoin", _mesh_key(m), _sig(left), _sig(right), out_cap)
        fn = _jit_cache.get(key)
        if fn is None:
            ax = config.data_axis

            def body(p_arrays, b_arrays, pcounts, bcount):
                op, ob, cnt = cross_local(p_arrays, b_arrays, pcounts[0],
                                          bcount[0], out_cap)
                return op, ob, cnt[None]

            fn = named_jit("crossjoin", C.smap(
                body, in_specs=(P(ax), P(), P(ax), P()),
                out_specs=(P(ax), P(ax), P(ax)), mesh=m))
            _jit_cache[key] = fn
        out_p, out_b, cnts = fn(pa, ba, left.counts_device(),
                                jnp.asarray([right.nrows], dtype=jnp.int64))
        counts = np.asarray(jax.device_get(cnts)).reshape(-1).astype(np.int64)
        res = _assemble_join(left, right, [], [], lorder, rorder, out_p,
                             out_b, int(counts.sum()), counts, "cross",
                             suffixes)
        return shrink_to_fit(res)
    lorder, rorder, pa, ba = _probe_build_arrays(left, right, [], [])
    out_cap = round_capacity(max(left.nrows * right.nrows, 1))
    out_p, out_b, cnt = cross_local(pa, ba, jnp.asarray(left.nrows),
                                    jnp.asarray(right.nrows), out_cap)
    nrows = int(jax.device_get(cnt))
    return _assemble_join(left, right, [], [], lorder, rorder, out_p,
                         out_b, nrows, None, "cross", suffixes)


# ---------------------------------------------------------------------------
# window / cumulative / shift
# ---------------------------------------------------------------------------

@_traced
def window_table(t: Table, specs: Sequence[Tuple[str, str, Optional[int],
                                                 str]]) -> Table:
    """Row-aligned window transforms: specs = [(col, op, param, outname)].
    ops: cumsum/cumprod/cummax/cummin, rolling_{sum,mean,min,max,count}
    (param = window), shift/diff (param = periods).

    Cross-shard state: cumulative carries exscan over the mesh; rolling
    and shift halos ride a ppermute ring shift (reference: rolling halo
    exchange bodo/hiframes/rolling.py, dist_cumsum via MPI_Exscan)."""
    from bodo_tpu.ops import window as W
    specs = [(c, op, p, o) for c, op, p, o in specs]
    names = t.names
    key = ("window", _mesh_key(mesh_mod.get_mesh()), _sig(t),
           tuple(specs), t.distribution)
    fn = _jit_cache.get(key)
    if fn is None:
        ax = config.data_axis

        def body(tree, counts, sharded: bool):
            count = counts[0] if sharded else counts
            out = {}
            if sharded:
                goff = C.dist_exscan_sum(count, ax)
            else:
                goff = jnp.asarray(0, jnp.int64)
            for col, op, param, oname in specs:
                x, v = tree[col]
                if op.startswith("cum"):
                    loc, carry = W.cum_local(op, x, v, count)
                    if sharded:
                        prefix = W.cum_carry_exscan(op, carry, ax)
                        loc = W.cum_combine(op, loc, prefix)
                    comb = W.cum_finalize(op, loc, x, v, count)
                    out[oname] = (comb, None)
                elif op.startswith("rolling_"):
                    w = int(param)
                    if sharded and w > 1:
                        # halo spans as many predecessor shards as
                        # needed (short/empty donors included)
                        hx, hok = W.multi_hop_halo(x, v, count, w - 1, ax)
                    else:  # single block: no predecessor
                        hx = jnp.zeros(max(w - 1, 0))
                        hok = jnp.zeros(max(w - 1, 0), bool)
                    res = W.rolling_local(op[len("rolling_"):], w, x, v,
                                          count, hx, hok, goff)
                    out[oname] = (res, None)
                elif op == "rowid":
                    cap = x.shape[0]
                    padmask = K.row_mask(count, cap)
                    rid = goff + jnp.arange(cap, dtype=jnp.int64)
                    out[oname] = (jnp.where(padmask, rid, -1), None)
                elif op in ("shift", "diff"):
                    n = int(param)
                    if sharded:
                        hx, hok = W.multi_hop_halo(x, v, count, n, ax)
                    else:
                        hx = jnp.zeros(n)
                        hok = jnp.zeros(n, bool)
                    sh, sok = W.shift_local(x, v, count, hx, hok, n)
                    if op == "diff":
                        cap = x.shape[0]
                        padmask = K.row_mask(count, cap)
                        ok = K.value_ok(x, v, padmask) & sok
                        sh = jnp.where(ok, x.astype(jnp.float64) - sh,
                                       jnp.nan)
                    out[oname] = (sh, None)
                else:
                    raise ValueError(f"unknown window op {op}")
            return out

        if t.distribution == ONED:
            m = mesh_mod.get_mesh()

            def sharded_fn(tree, counts):
                return body(tree, counts, True)
            fn = named_jit("window", C.smap(
                sharded_fn, in_specs=(P(ax), P(ax)), out_specs=P(ax), mesh=m))
        else:
            def rep_fn(tree, counts):
                return body(tree, counts, False)
            fn = named_jit("window", rep_fn)
        _jit_cache[key] = fn

    counts = t.counts_device() if t.distribution == ONED \
        else jnp.asarray(t.nrows)
    out_tree = fn(t.device_data(), counts)
    res = t.with_columns(t.columns)
    for col, op, param, oname in specs:
        d, v = out_tree[oname]
        res.columns[oname] = Column(
            d, v, dt.INT64 if op == "rowid" else dt.FLOAT64, None)
    return res


def rank_window(t: Table, partition_by: Sequence[str],
                order_by: Sequence[str],
                specs: Sequence[Tuple[str, int, str]],
                ascending=None, na_last: bool = True) -> Table:
    """Partitioned ranking windows: specs = [(op, param, outname)] with op
    in row_number/rank/dense_rank/ntile/cumcount (reference:
    bodo/libs/window/_window_aggfuncs.cpp family).

    Distributed strategy: hash-shuffle rows so each partition is wholly
    on one shard, rank locally, then restore the original row order via a
    rowid sample-sort (keeps pandas transform alignment)."""
    partition_by = list(partition_by)
    order_by = list(order_by)
    if ascending is None:
        ascending = [True] * len(order_by)
    elif isinstance(ascending, bool):
        ascending = [ascending] * len(order_by)

    local = _as_local(t)
    if local is not None:
        t = local
    if t.distribution == ONED:
        if not partition_by:
            # global ranking: distributed sample sort on the order keys,
            # then exscan'd row offsets + cross-shard tie carries — no
            # gather (reference: streaming window over sorted runs,
            # bodo/libs/streaming/_window.cpp)
            return _global_rank_sharded(t, order_by, specs,
                                        tuple(ascending), na_last)
        keep = t.names
        t2 = window_table(t, [(t.names[0], "rowid", None, "__rid")])
        t2 = shuffle_by_key(t2, partition_by)
        out = _rank_window_exec(t2, partition_by, order_by, specs,
                                tuple(ascending), na_last)
        out = sort_table(out, ["__rid"])
        return out.select(keep + [o for _, _, o in specs])
    return _rank_window_exec(t, partition_by, order_by, specs,
                             tuple(ascending), na_last)


def _global_rank_sharded(t: Table, order_by, specs, ascending,
                         na_last: bool) -> Table:
    """No-partition ranking over the whole table, distributed: sort by
    the order keys (sample sort), then compute ranks with exscan row
    offsets and typed cross-shard tie detection; restore original row
    order via the carried rowid."""
    from bodo_tpu.ops import window as W
    keep = t.names
    t2 = window_table(t, [(t.names[0], "rowid", None, "__rid")])
    if order_by:
        t2 = sort_table(t2, list(order_by), list(ascending), na_last)
    else:
        # no ORDER BY: original row order is the total order already
        pass
    m = mesh_mod.get_mesh()
    ax = config.data_axis
    ob = list(order_by)
    kspecs = tuple((op, int(p or 0), o) for op, p, o in specs)
    key = ("grank", _mesh_key(m), _sig(t2), tuple(ob), kspecs,
           t2.distribution)
    fn = _jit_cache.get(key)
    if fn is None:
        def body(tree, counts):
            count = counts[0]
            some = tree["__rid"][0]
            cap = some.shape[0]
            padmask = K.row_mask(count, cap)
            goff = C.dist_exscan_sum(count, ax)
            total = C.dist_sum(count, ax)
            gidx = goff + jnp.arange(cap, dtype=jnp.int64)  # 0-based
            # tie flags: row differs from the previous real row in ANY
            # order column (typed compares; nulls tie with nulls)
            if ob:
                new = jnp.zeros(cap, bool)
                for name in ob:
                    x, v = tree[name]
                    pv, pok, pexists = W.prev_last_value(x, v, count, ax)
                    ok = K.value_ok(x, v, padmask)
                    prev_x = jnp.concatenate([pv[None], x[:-1]])
                    prev_ok = jnp.concatenate([pok[None], ok[:-1]])
                    first_global = (gidx == 0)
                    # nulls tie with nulls: value compare only when both
                    # sides are real; a validity transition breaks a run
                    diff = (ok & prev_ok & (prev_x != x)) | (prev_ok != ok)
                    # row 0 of shard compares against predecessor's last
                    # row; the very first global row always starts a run
                    is_first_local = jnp.arange(cap) == 0
                    no_pred = is_first_local & ~pexists
                    new = new | diff | no_pred | first_global
            else:
                # no ORDER BY: every row is a peer — one global run
                # (RANK/DENSE_RANK = 1; ROW_NUMBER still positional)
                new = gidx == 0
            # rank (min): global index of the run head ≤ this row.
            # local segment cummax + running-max carry across shards
            head = jnp.where(new & padmask, gidx, -1)
            loc = jax.lax.cummax(head)
            carry = jnp.max(jnp.where(padmask, head, -1))
            prefix = W.cum_carry_exscan("cummax", carry.astype(jnp.float64),
                                        ax)
            # shard 0's prefix is -inf; clamp to the head sentinel (-1)
            # before the int cast (float->int of -inf is saturation-
            # defined, not portable)
            prefix = jnp.maximum(prefix, -1.0).astype(jnp.int64)
            run_head = jnp.maximum(loc, prefix)
            # dense rank: cumsum of run-head flags + exscan carry
            nf = (new & padmask).astype(jnp.int64)
            dloc = jnp.cumsum(nf)
            dcarry = jnp.sum(nf)
            dprefix = W.cum_carry_exscan("cumsum",
                                         dcarry.astype(jnp.float64), ax)
            dense = dloc + dprefix.astype(jnp.int64)
            out = []
            for op, param, _ in kspecs:
                if op == "row_number":
                    r = gidx + 1
                elif op == "cumcount":
                    r = gidx
                elif op == "rank":
                    r = run_head + 1
                elif op == "dense_rank":
                    r = dense
                elif op == "ntile":
                    n = jnp.asarray(param, jnp.int64)
                    small = total // n
                    rem = total - small * n
                    # first `rem` buckets get (small+1) rows
                    cut = rem * (small + 1)
                    r = jnp.where(
                        gidx < cut,
                        gidx // jnp.maximum(small + 1, 1),
                        rem + (gidx - cut) // jnp.maximum(small, 1)) + 1
                else:
                    raise ValueError(f"unknown rank op {op}")
                out.append(jnp.where(padmask, r.astype(jnp.int64), 0))
            return tuple(out)

        fn = named_jit("window_rank_global", C.smap(
            body, in_specs=(P(ax), P(ax)), out_specs=P(ax), mesh=m))
        _jit_cache[key] = fn
    outs = fn(t2.device_data(), t2.counts_device())
    res = t2.with_columns(t2.columns)
    for (op, p, oname), d in zip(kspecs, outs):
        res.columns[oname] = Column(d, None, dt.INT64, None)
    res = sort_table(res, ["__rid"])
    return res.select(keep + [o for _, _, o in specs])


def _rank_window_exec(t: Table, partition_by, order_by, specs,
                      ascending: Tuple[bool, ...], na_last: bool) -> Table:
    from bodo_tpu.ops.window import rank_window_local

    kspecs = tuple((op, int(param or 0)) for op, param, _ in specs)
    key = ("rankwin", _mesh_key(mesh_mod.get_mesh()), _sig(t),
           tuple(partition_by), tuple(order_by), kspecs, ascending,
           na_last, t.distribution)
    fn = _jit_cache.get(key)
    if fn is None:
        pk, ob = list(partition_by), list(order_by)

        def body(tree, count):
            ka = tuple(tree[n] for n in pk)
            oa = tuple(tree[n] for n in ob)
            return rank_window_local(ka, oa, count, kspecs, len(pk),
                                     ascending, na_last)

        if t.distribution == ONED:
            m = mesh_mod.get_mesh()
            ax = config.data_axis

            def sharded(tree, counts):
                return body(tree, counts[0])
            fn = named_jit("window_rank", C.smap(
                sharded, in_specs=(P(ax), P(ax)), out_specs=P(ax), mesh=m))
        else:
            fn = named_jit("window_rank", body)
        _jit_cache[key] = fn

    counts = t.counts_device() if t.distribution == ONED \
        else jnp.asarray(t.nrows)
    outs = fn(t.device_data(), counts)
    res = t.with_columns(t.columns)
    for (op, param, oname), d in zip(specs, outs):
        res.columns[oname] = Column(d, None, dt.INT64, None)
    return res


def agg_window(t: Table, partition_by: Sequence[str],
               order_by: Sequence[str],
               specs: Sequence[Tuple[str, str, tuple, int, str]],
               ascending=None, na_last: bool = True) -> Table:
    """Aggregate/navigation windows: specs = [(op, col, frame, param,
    outname)] with op in sum/mean/count/min/max/lead/lag/first_value/
    last_value and frame in ("all",) / ("cumrange",) / ("rows", lo, hi)
    (reference: bodo/libs/window/_window_aggfuncs.cpp,
    bodo/libs/_lead_lag.cpp).

    Distributed strategy mirrors rank_window: hash-shuffle whole
    partitions onto shards, run the sorted-pass kernel locally, restore
    the original row order via a rowid sample-sort."""
    partition_by = list(partition_by)
    order_by = list(order_by)
    if ascending is None:
        ascending = [True] * len(order_by)
    elif isinstance(ascending, bool):
        ascending = [ascending] * len(order_by)

    local = _as_local(t)
    if local is not None:
        t = local
    if t.distribution == ONED:
        if not partition_by:
            whole = (not order_by) and all(
                tuple(frame) == ("all",) and
                op in ("sum", "sum0", "mean", "min", "max", "count")
                for op, _, frame, *_ in specs)
            if whole:
                # SUM(x) OVER () etc.: one distributed reduction
                # (psum-combined partials), broadcast back — no gather
                rmap = {"sum": "sumnull", "sum0": "sum"}
                vals = reduce_table(
                    t, [(c, rmap.get(op, op), o)
                        for op, c, frame, p, o in specs])
                res = t.with_columns(dict(t.columns))
                for op, c, frame, p, o in specs:
                    res.columns[o] = _broadcast_scalar_column(
                        t, vals[o], count_like=(op == "count"))
                return res
            # ordered global frames (running totals over a total order)
            # still gather — rare at scale; the sorted+carry treatment
            # used by _global_rank_sharded extends here later
            return agg_window(t.gather(), partition_by, order_by, specs,
                              ascending, na_last).shard()
        keep = t.names
        t2 = window_table(t, [(t.names[0], "rowid", None, "__rid")])
        t2 = shuffle_by_key(t2, partition_by)
        exec_order, exec_asc = list(order_by), list(ascending)
        if not exec_order and any(
                op in ("lead", "lag", "first_value", "last_value")
                or frame[0] != "all"
                for op, _, frame, *_ in specs):
            # order-sensitive specs with no ORDER BY follow the original
            # row order — the shuffle may interleave source shards, so
            # pin the sort to the global rowid
            exec_order, exec_asc = ["__rid"], [True]
        out = _agg_window_exec(t2, partition_by, exec_order, specs,
                               tuple(exec_asc), na_last)
        out = sort_table(out, ["__rid"])
        return out.select(keep + [o for *_, o in specs])
    return _agg_window_exec(t, partition_by, order_by, specs,
                            tuple(ascending), na_last)


def _broadcast_scalar_column(t: Table, v, count_like: bool) -> Column:
    """A whole-table scalar broadcast to every row of a (possibly
    sharded) table — the OVER () window result column."""
    import datetime as _dtmod
    import decimal as pydec

    import pandas as pd
    cap = t.capacity
    invalid = False
    if count_like:
        arr = np.full(cap, 0 if v is None else int(v), np.int64)
        dtype = dt.INT64
    elif v is None or (isinstance(v, float) and np.isnan(v)) or v is pd.NaT:
        arr = np.zeros(cap, np.float64)
        dtype = dt.FLOAT64
        invalid = True
    elif isinstance(v, pd.Timestamp):
        arr = np.full(cap, v.value, np.int64)
        dtype = dt.DATETIME
    elif isinstance(v, (pd.Timedelta, np.timedelta64)):
        ns = pd.Timedelta(v).value
        arr = np.full(cap, ns, np.int64)
        dtype = dt.TIMEDELTA
    elif isinstance(v, _dtmod.date) and not isinstance(v, _dtmod.datetime):
        days = (np.datetime64(v, "D") - np.datetime64(0, "D")).astype(int)
        arr = np.full(cap, days, np.int32)
        dtype = dt.DATE
    elif isinstance(v, pydec.Decimal):
        # keep the exact fixed-point domain (scaled int64)
        scale = max(0, -int(v.as_tuple().exponent))
        arr = np.full(cap, int(v.scaleb(scale)), np.int64)
        dtype = dt.decimal(scale)
    elif isinstance(v, (bool, np.bool_)):
        arr = np.full(cap, bool(v), bool)
        dtype = dt.BOOL
    elif isinstance(v, (int, np.integer)):
        arr = np.full(cap, int(v), np.int64)
        dtype = dt.INT64
    else:
        arr = np.full(cap, float(v), np.float64)
        dtype = dt.FLOAT64
    if t.distribution == ONED:
        data = jax.device_put(arr, mesh_mod.row_sharding())
        valid = (jax.device_put(np.zeros(cap, bool),
                                mesh_mod.row_sharding())
                 if invalid else None)
    else:
        data = jnp.asarray(arr)
        valid = jnp.asarray(np.zeros(cap, bool)) if invalid else None
    return Column(data, valid, dtype, None)


def _agg_window_exec(t: Table, partition_by, order_by, specs,
                     ascending: Tuple[bool, ...], na_last: bool) -> Table:
    from bodo_tpu.ops.window import agg_window_local

    val_cols = list(dict.fromkeys(c for _, c, *_ in specs))
    vidx = {c: i for i, c in enumerate(val_cols)}
    kspecs = tuple((op, vidx[c], tuple(frame), int(param or 0))
                   for op, c, frame, param, _ in specs)
    key = ("aggwin", _mesh_key(mesh_mod.get_mesh()), _sig(t),
           tuple(partition_by), tuple(order_by), kspecs, ascending,
           na_last, t.distribution)
    fn = _jit_cache.get(key)
    if fn is None:
        pk, ob, vc = list(partition_by), list(order_by), list(val_cols)

        def body(tree, count):
            ka = tuple(tree[n] for n in pk)
            oa = tuple(tree[n] for n in ob)
            va = tuple(tree[n] for n in vc)
            return agg_window_local(ka, oa, va, count, kspecs, len(pk),
                                    ascending, na_last)

        if t.distribution == ONED:
            m = mesh_mod.get_mesh()
            ax = config.data_axis

            def sharded(tree, counts):
                return body(tree, counts[0])
            fn = named_jit("window_agg", C.smap(
                sharded, in_specs=(P(ax), P(ax)), out_specs=P(ax), mesh=m))
        else:
            fn = named_jit("window_agg", body)
        _jit_cache[key] = fn

    counts = t.counts_device() if t.distribution == ONED \
        else jnp.asarray(t.nrows)
    outs = fn(t.device_data(), counts)
    res = t.with_columns(t.columns)
    for (op, col, frame, param, oname), (d, v) in zip(specs, outs):
        src = t.column(col)
        if op in ("lead", "lag", "first_value", "last_value"):
            # gather ops carry the source dtype (and dictionary)
            res.columns[oname] = Column(d, v, src.dtype, src.dictionary, src.vrange)
        else:
            # same dtype/descale rules as groupby aggregation outputs
            # (sum0 = pandas-style sum: 0 over empty frames, same dtype)
            res.columns[oname] = _agg_out_col(
                src, "sum" if op == "sum0" else op, d, v)
    return res


# ---------------------------------------------------------------------------
# whole-column reductions
# ---------------------------------------------------------------------------

_REDUCE_PARTIALS = {"sum": ("sum",), "sumnull": ("sum", "count"),
                    "count": ("count",), "size": ("size",),
                    "min": ("min", "count"), "max": ("max", "count"),
                    "mean": ("sum", "count"),
                    "var": ("sum", "m2", "count"),
                    "std": ("sum", "m2", "count"),
                    "var0": ("sum", "m2", "count"),
                    "std0": ("sum", "m2", "count"),
                    "prod": ("prod",)}


def reduce_table(t: Table, aggs: Sequence[Tuple[str, str, str]]) -> Dict:
    """Whole-column reductions → host scalars (Series.sum() analogue).

    Per-shard partials are one fused jitted pass (masked reductions on the
    VPU); the tiny [S, n_partials] result combines on host — the same
    partial/combine decomposition as the distributed groupby. Order
    statistics (median/quantile) take a sort-based path instead
    (reference: bodo/libs/_quantile_alg.cpp).
    """
    qaggs = [(c, op, o) for c, op, o in aggs
             if op == "median" or op.startswith("quantile_")]
    if qaggs:
        aggs = [(c, op, o) for c, op, o in aggs
                if not (op == "median" or op.startswith("quantile_"))]
        out = reduce_table(t, aggs) if aggs else {}
        for c, op, o in qaggs:
            q = 0.5 if op == "median" else float(op[len("quantile_"):])
            out[o] = _reduce_quantile(t, c, q)
        return out

    # ops with no scalar-partial form (skew/kurt/mode/listagg/nunique)
    # reduce via a constant-key groupby — one group, same kernels
    gaggs = [(c, op, o) for c, op, o in aggs
             if op not in _REDUCE_PARTIALS]
    if gaggs:
        aggs = [(c, op, o) for c, op, o in aggs
                if op in _REDUCE_PARTIALS]
        out = reduce_table(t, aggs) if aggs else {}
        zeros = np.zeros((t.capacity,), np.int32)
        if t.distribution == ONED:
            kd = jax.device_put(zeros, mesh_mod.row_sharding())
        else:
            kd = jnp.asarray(zeros)
        tk = t.with_columns(dict(t.columns))
        tk.columns["__one"] = Column(kd, None, dt.INT32, None)
        g = groupby_agg(tk, ["__one"], gaggs)
        gp = g.to_pandas()
        for _, _, o in gaggs:
            out[o] = gp[o].iloc[0] if len(gp) else None
        return out

    specs = []
    layout = []
    for col, op, _ in aggs:
        parts = _REDUCE_PARTIALS[op]
        layout.append((len(specs), parts))
        specs.extend((col, p) for p in parts)
    names = t.names
    key = ("reduce", _sig(t), tuple(specs), t.distribution,
           _mesh_key(mesh_mod.get_mesh()) if t.distribution == ONED else None)
    fn = _jit_cache.get(key)
    if fn is None:
        def body(tree, count):
            cap = tree[names[0]][0].shape[0]
            padmask = K.row_mask(count, cap)
            outs = []
            for col, p in specs:
                d, v = tree[col]
                ok = K.value_ok(d, v, padmask)
                if p == "count":
                    outs.append(jnp.sum(ok).astype(jnp.int64))
                elif p == "size":
                    outs.append(jnp.sum(padmask).astype(jnp.int64))
                elif p == "sum":
                    # exact in the widened source family (int64/float64)
                    acc = jnp.float64 if jnp.issubdtype(d.dtype, jnp.floating) \
                        else (jnp.uint64 if jnp.issubdtype(
                            d.dtype, jnp.unsignedinteger) else jnp.int64)
                    x = d.astype(acc)
                    outs.append(jnp.sum(jnp.where(ok, x, jnp.zeros((), x.dtype))))
                elif p == "m2":
                    # stable centered second moment, float64 (Chan combine
                    # on host; reference bodo/libs/groupby/_groupby_update
                    # .cpp var_combine)
                    x = d.astype(jnp.float64)
                    s = jnp.sum(jnp.where(ok, x, 0.0))
                    n = jnp.maximum(jnp.sum(ok), 1).astype(jnp.float64)
                    dd = jnp.where(ok, x - s / n, 0.0)
                    outs.append(jnp.sum(dd * dd))
                elif p == "prod":
                    outs.append(jnp.prod(jnp.where(ok, d.astype(jnp.float64),
                                                   1.0)))
                elif p in ("min", "max"):
                    # keep the source dtype — int64 ns ticks stay exact
                    if jnp.issubdtype(d.dtype, jnp.floating):
                        ident = jnp.array(np.inf if p == "min" else -np.inf,
                                          d.dtype)
                    elif d.dtype == jnp.bool_:
                        ident = jnp.array(p == "min", jnp.bool_)
                    else:
                        info = jnp.iinfo(d.dtype)
                        ident = jnp.array(info.max if p == "min"
                                          else info.min, d.dtype)
                    f = jnp.min if p == "min" else jnp.max
                    outs.append(f(jnp.where(ok, d, ident)))
            return tuple(outs)

        if t.distribution == ONED:
            m = mesh_mod.get_mesh()
            ax = config.data_axis

            def sharded(tree, counts):
                return tuple(o[None] for o in body(tree, counts[0]))
            fn = named_jit("reduce", C.smap(
                sharded, in_specs=(P(ax), P(ax)),
                out_specs=tuple(P(ax) for _ in specs), mesh=m))
        else:
            def rep(tree, count):
                return tuple(o[None] for o in body(tree, count))
            fn = named_jit("reduce", rep)
        _jit_cache[key] = fn

    counts_in = t.counts_device() if t.distribution == ONED \
        else jnp.asarray(t.nrows)
    raw = jax.device_get(fn(t.device_data(), counts_in))
    partials = [np.asarray(r).reshape(-1) for r in raw]
    out = {}
    for (col, op, oname), (off, parts) in zip(aggs, layout):
        block = {p: partials[off + i] for i, p in enumerate(parts)}
        cnt = int(block["count"].sum()) if "count" in block else None
        if op == "sum":
            v = block["sum"].sum()
        elif op == "sumnull":
            v = block["sum"].sum() if cnt else np.nan
        elif op == "prod":
            v = np.prod(block["prod"])
        elif op in ("count", "size"):
            v = int(block[op].sum())
        elif op in ("min", "max"):
            if cnt == 0:
                out[oname] = np.nan
                continue
            v = block[op].min() if op == "min" else block[op].max()
        elif op == "mean":
            v = float(block["sum"].sum()) / cnt if cnt else np.nan
        elif op in ("var", "std", "var0", "std0"):
            ddof = 0 if op.endswith("0") else 1
            if cnt is not None and cnt > ddof:
                # exact delta-form Chan combine of per-shard moments
                n_i = block["count"].astype(np.float64)
                s_i = block["sum"].astype(np.float64)
                m = s_i.sum() / cnt
                mean_i = s_i / np.maximum(n_i, 1)
                m2 = block["m2"].sum() + (n_i * (mean_i - m) ** 2).sum()
                v = max(m2 / (cnt - ddof), 0.0)
                if op.startswith("std"):
                    v = float(np.sqrt(v))
            else:
                v = np.nan
        out[oname] = _reduce_scalar(v, op, t.column(col).dtype, cnt)
    return out


def _reduce_quantile(t: Table, col: str, q: float) -> float:
    """Linear-interpolated whole-column quantile. 1D tables gather the
    single column (the exact-selection distributed variant is a later
    refinement; the reference gathers for exact quantiles too at this
    size)."""
    src = t.select([col])
    if src.distribution == ONED:
        src = src.gather()
    key = ("reduceq", _sig(src), src.capacity)
    fn = _jit_cache.get(key)
    if fn is None:
        def body(tree, count):
            d, v = tree[col]
            cap = d.shape[0]
            ok = K.value_ok(d, v, K.row_mask(count, cap))
            enc_last = jnp.where(ok, jnp.zeros((), jnp.uint8),
                                 jnp.ones((), jnp.uint8))
            s_rank, s_val = jax.lax.sort(
                (enc_last, d.astype(jnp.float64)), num_keys=2,
                is_stable=False)
            cnt = jnp.sum(ok)
            return s_val, cnt

        fn = named_jit("reduce_quantile", body)
        _jit_cache[key] = fn
    s_val, cnt = fn(src.device_data(), jnp.asarray(src.nrows))
    n = int(jax.device_get(cnt))
    if n == 0:
        return float("nan")
    qpos = (n - 1) * q
    lo, hi = int(np.floor(qpos)), int(np.ceil(qpos))
    vals = np.asarray(jax.device_get(s_val[lo:hi + 1]))
    out = float(vals[0]) if lo == hi else \
        float(vals[0] + (vals[1] - vals[0]) * (qpos - lo))
    if dt.is_decimal(src.column(col).dtype):
        out /= 10.0 ** src.column(col).dtype.scale
    return out


def _reduce_scalar(v, op: str, src: dt.DType, cnt: Optional[int]):
    """Convert a host reduction result back to its logical scalar type."""
    import pandas as pd
    if op in ("count", "size"):
        return int(v)
    if dt.is_decimal(src):
        import decimal as pydec
        if op == "prod":
            raise NotImplementedError("prod over a decimal column")
        if op in ("sum", "sumnull", "min", "max", "first", "last"):
            if isinstance(v, float) and np.isnan(v):
                return v
            return pydec.Decimal(int(v)).scaleb(-src.scale)
        # mean/var/std: physical float → descale
        f = 10.0 ** (2 * src.scale) if op in ("var", "var0") \
            else 10.0 ** src.scale
        return float(v) / f
    if op in ("min", "max", "first", "last"):
        if src is dt.DATETIME:
            return pd.Timestamp(int(v)) if v is not None else pd.NaT
        if src is dt.TIMEDELTA:
            return pd.Timedelta(int(v))
        if src is dt.DATE:
            return (np.datetime64(0, "D") + int(v)).astype("datetime64[D]")
        if src.kind in ("i", "u"):
            return int(v)
        if src.kind == "b":
            return bool(v)
        return float(v)
    if op in ("sum", "sumnull", "prod") and src.kind in ("i", "u", "b"):
        return int(v) if not (isinstance(v, float) and np.isnan(v)) else v
    return float(v)


# ---------------------------------------------------------------------------
# capacity hygiene
# ---------------------------------------------------------------------------

def _shrink_fn(S: int, old_cap: int, new_cap: int):
    key = ("shrink", S, old_cap, new_cap)
    fn = _jit_cache.get(key)
    if fn is None:
        @partial(named_jit, "shrink_to_fit")
        def fn(tree):
            out = {}
            for n, (d, v) in tree.items():
                d2 = d.reshape(S, old_cap)[:, :new_cap].reshape(S * new_cap)
                v2 = None if v is None else \
                    v.reshape(S, old_cap)[:, :new_cap].reshape(S * new_cap)
                out[n] = (d2, v2)
            return out
        _jit_cache[key] = fn
    return fn


def shrink_to_fit(t: Table) -> Table:
    """Shrink per-shard capacity to fit the real row counts (device-side
    slice; rows are already compacted to the front of each shard). This is
    the padding-hygiene step that keeps downstream sorts/shuffles sized to
    the data, not to worst-case capacities."""
    if t.distribution == ONED:
        S = t.num_shards
        old = t.shard_capacity
        new = round_capacity(int(t.counts.max()) if len(t.counts) else 1)
        if new >= old:
            return t
        tree = _shrink_fn(S, old, new)(t.device_data())
        return t.with_device_data(tree, nrows=t.nrows, counts=t.counts)
    old = t.capacity
    new = round_capacity(max(t.nrows, 1))
    if new >= old:
        return t
    tree = {n: (c.data[:new], None if c.valid is None else c.valid[:new])
            for n, c in t.columns.items()}
    return t.with_device_data(tree, nrows=t.nrows)


def _build_shuffle_fn(mesh_key, nk: int, cap: int, sig_key, has_valid):
    """The shard_map program of `shuffle_by_key`: the first `nk` arrays
    are the keys, `cap` a shard's capacity and every bucket's (a shard
    can receive at most what all hold, so no bucket overflows), and
    `has_valid` says which arrays carry a null mask."""
    key = ("shuffle", mesh_key, sig_key, nk, cap)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    mesh = _MESHES[mesh_key]
    S = mesh_mod.num_shards(mesh)
    ax = config.data_axis

    def body(arrs, counts):
        dest = dest_shard(hash_columns(arrs[:nk]), S)
        flat, _ = _flatten_with_valids(arrs)
        out, cnt2, _ = shuffle_rows(dest, flat, counts[0], S, cap, ax)
        return _rebuild_from_flat(out, has_valid), cnt2[None]

    fn = named_jit("shuffle_by_key", C.smap(
        body, in_specs=(P(ax), P(ax)), out_specs=(P(ax), P(ax)),
        mesh=mesh))
    _jit_cache[key] = fn
    return fn


def shuffle_by_key(t: Table, key_cols: Sequence[str]) -> Table:
    """Hash-partition rows over the mesh by key columns (the standalone
    shuffle_table analogue, reference bodo/libs/_shuffle.h:41). Rows with
    equal keys land on the same shard."""
    if t.distribution != ONED:
        from bodo_tpu.analysis.plan_validator import PlanInvariantError
        raise PlanInvariantError(
            "shuffle_by_key over a replicated table: the shuffle "
            "contract requires a row-sharded (1D) input — shard the "
            "table first (physical._maybe_shard) or keep the whole op "
            "on the replicated path", rule="shuffle-needs-1d")
    # lockstep fingerprint only — no maybe_inject here: the `collective`
    # fault point fires at the groupby/sort/join dispatchers above this
    # call, and adding a second firing site would shift chaos tests'
    # nth-call counting
    wait = 0.0
    if t.num_shards > 1:
        from bodo_tpu.analysis import lockstep
        wait = lockstep.pre_collective("shuffle_by_key")
    from bodo_tpu.parallel import comm
    from bodo_tpu.plan import adaptive
    adaptive.observe_shuffle(t, key_cols)
    with tracing.event("shuffle_by_key", keys=list(key_cols)) as ev, \
            comm.collective_span("shuffle_by_key",
                                 bytes_in=comm.table_bytes(t),
                                 wait_s=wait) as sp:
        if ev is not None:
            ev["rows"] = t.nrows
        m = mesh_mod.get_mesh()
        names = t.names
        nk = len(key_cols)
        korder = list(key_cols) + [n for n in names if n not in key_cols]
        fn = _build_shuffle_fn(
            _mesh_key(m), nk, t.shard_capacity, _sig(t.select(korder)),
            tuple(t.column(n).valid is not None for n in korder))
        karrays = tuple((t.column(n).data, t.column(n).valid)
                        for n in korder)
        out, cnts = fn(karrays, t.counts_device())
        counts = np.asarray(jax.device_get(cnts)).reshape(-1).astype(
            np.int64)
        tree = {n: out[i] for i, n in enumerate(korder)}
        res = t.with_device_data(tree, nrows=int(counts.sum()),
                                 counts=counts)
        out_t = _keep_vranges(shrink_to_fit(res.select(names)), t)
        sp["bytes_out"] = comm.table_bytes(out_t)
        return out_t


def shard_frames(t: Table) -> List:
    """Decode each shard of a 1D table into its own host DataFrame
    (rank-local view after a shuffle — the frame a reference worker
    would hold; used by groupby.apply's per-shard UDF execution)."""
    if t.distribution != ONED:
        return [t.to_pandas()]
    per = t.shard_capacity
    out = []
    for s in range(t.num_shards):
        cols = {}
        for n, c in t.columns.items():
            sl = slice(s * per, (s + 1) * per)
            cols[n] = Column(c.data[sl],
                             None if c.valid is None else c.valid[sl],
                             c.dtype, c.dictionary)
        sub = Table(cols, int(t.counts[s]), REP, None)
        out.append(sub.to_pandas())
    return out


# ---------------------------------------------------------------------------
# concat / union all
# ---------------------------------------------------------------------------

def concat_tables(tables: Sequence[Table]) -> Table:
    """Row-wise concatenation (UNION ALL). Inputs must share the schema;
    string dictionaries are unified; numeric dtypes promote.

    TODO(next round): shard-wise append + rebalance instead of the
    gather-to-host path (keeps large unions device-resident). The
    current gather path's REP result is a DECLARED invariant
    (analysis/plan_validator.RUNTIME_RESULT_DIST["union"], cross-checked
    below): the shard-wise rewrite must update that declaration and
    Union's OP_DIST propagation rule in the same change, or the check
    at the bottom of this function fires."""
    assert tables
    names = tables[0].names
    parts = [t.gather() if t.distribution == ONED else t for t in tables]
    total = sum(t.nrows for t in parts)
    cap = round_capacity(max(total, 1))
    cols: Dict[str, Column] = {}
    for n in names:
        src_cols = [t.columns[n] for t in parts]
        if any(c.dtype is dt.STRING for c in src_cols):
            _, src_cols = unify_dictionaries(src_cols)
            out_dtype = dt.STRING
            dictionary = src_cols[0].dictionary
        elif any(dt.is_decimal(c.dtype) for c in src_cols):
            scales = {c.dtype.scale for c in src_cols
                      if dt.is_decimal(c.dtype)}
            if len(scales) == 1 and all(dt.is_decimal(c.dtype)
                                        for c in src_cols):
                out_dtype = dt.decimal(
                    scales.pop(),
                    precision=max(c.dtype.precision for c in src_cols))
            else:  # mixed scales / decimal+float: descale to float64
                out_dtype = dt.FLOAT64
                src_cols = [
                    Column(c.data / 10.0 ** c.dtype.scale, c.valid,
                           dt.FLOAT64, None)
                    if dt.is_decimal(c.dtype) else c for c in src_cols]
            dictionary = None
        else:
            out_np = np.result_type(*[c.dtype.numpy for c in src_cols])
            out_dtype = dt.from_numpy(out_np)
            dictionary = None
        datas, valids = [], []
        any_valid = any(c.valid is not None for c in src_cols)
        for t, c in zip(parts, src_cols):
            datas.append(c.data[: t.nrows].astype(out_dtype.numpy)
                         if c.data.dtype != out_dtype.numpy
                         else c.data[: t.nrows])
            if any_valid:
                valids.append(c.valid[: t.nrows] if c.valid is not None
                              else jnp.ones(t.nrows, dtype=bool))
        data = jnp.zeros((cap,), dtype=out_dtype.numpy)
        data = data.at[:total].set(jnp.concatenate(datas) if datas
                                   else data[:0])
        valid = None
        if any_valid:
            valid = jnp.zeros((cap,), dtype=bool)
            valid = valid.at[:total].set(jnp.concatenate(valids))
        cols[n] = Column(data, valid, out_dtype, dictionary)
    out = Table(cols, total, REP, None)
    from bodo_tpu.analysis.plan_validator import check_kernel_result
    check_kernel_result("union", out.distribution)
    return out


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def head_table(t: Table, n: int) -> Table:
    g = t.gather() if t.distribution == ONED else t
    n = min(n, g.nrows)
    return Table(dict(g.columns), n, REP, None)


# Re-bucket a table's physical capacity when occupancy falls below this.
REBUCKET_THRESHOLD = 0.45


def rebucket_capacity(nrows: int, capacity: int) -> int:
    """The capacity `rebucket` leaves a replicated table of `nrows` rows
    in `capacity` slots, for a producer that can be born there."""
    if max(nrows, 1) / capacity >= REBUCKET_THRESHOLD:
        return capacity
    return min(capacity, round_capacity(max(nrows, 1)))


def rebucket(t: Table) -> Table:
    """Shrink physical capacity when occupancy drops below the threshold
    (the re-bucketing step of the padded-capacity design, SURVEY.md §7)."""
    occupancy_cap = (max(t.counts.max(), 1) * t.num_shards
                     if t.distribution == ONED and len(t.counts)
                     else max(t.nrows, 1))
    if occupancy_cap / t.capacity >= REBUCKET_THRESHOLD:
        return t
    return shrink_to_fit(t)
