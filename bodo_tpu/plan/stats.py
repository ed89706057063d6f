"""Cardinality estimation for plan nodes, and the greedy join order
that runs on it.

Replaces the role of the reference's vendored-DuckDB cost model
(bodo/pandas/plan.py get_plan_cardinality, _plan.cpp) with a compact
estimator: exact row counts from scan metadata (parquet footers are
free), textbook selectivity factors for predicates, and the
|L|·|R|/max(ndv) join formula. ndv(key) is the raw row count of the
smaller side (exact when the key is that side's primary key), capped
by a bound on the key's distinct values where the sources give one:
the value range of a resident integer/date/bool column or the length
of a string column's dictionary (`key_ndv_bound`, one min/max
reduction in a resident column's life), parquet footer statistics for
a scan. Float, datetime and computed keys give no bound, and then the
row count stands alone.

`estimate(node)` returns (est_rows, raw_rows): est is the post-filter
expectation, raw the unfiltered size of the underlying relation —
the pair is what the greedy join-ordering needs to tell "small because
the table is small" from "small because a filter is selective".

`greedy_join_order` is the one join-ordering loop of the package: the
SQL planner (`sql/planner._plan_from_where`), the frame path
(`optimizer.reorder_joins`) and, through the latter, the run-time
re-plan (`adaptive.maybe_reoptimize_join`) all call it, so the static
and the run-time pass price a join with the same function on the same
bounds.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

from bodo_tpu.plan import logical as L
from bodo_tpu.plan.expr import (BinOp, ColRef, Expr, IsIn, StrPredicate,
                                UnOp)

# runtime-stats override installed by plan/adaptive.py:
# fn(node) -> Optional[observed rows]; estimate() consults it first so
# executed subplans feed their ACTUAL cardinality back into planning.
_runtime_override = None

# cached row counts keyed by the dataset's content signature (resolved
# file list + mtimes) — an overwritten dataset changes signature and
# naturally misses instead of reusing stale counts
_pq_rows_cache: Dict[Tuple, int] = {}
_pq_span_cache: Dict[Tuple, Optional[int]] = {}
_warned_unknown: Set[str] = set()

# candidate joins priced with / without a key bound (adaptive.stats()
# reports them as join_est_keyed / join_est_unkeyed)
_priced = {"keyed": 0, "unkeyed": 0}


def _dataset_sig(path) -> Tuple[Tuple, Tuple]:
    """(files, (mtime, size) stamps) of a parquet dataset — the
    row-count cache key and the persistent stats store's content
    signature. Built from the I/O layer's shared file signatures
    (io/parquet.file_signature), the same identity that keys the footer
    cache, so one stat() serves pushdown, planning, and AQE."""
    from bodo_tpu.io.parquet import dataset_signature
    sigs = dataset_signature(path)
    return (tuple(s[0] for s in sigs),
            tuple((s[1], s[2]) for s in sigs))


def _note_unknown(path) -> None:
    """One-time note (tracing + verbose log) when the 1M-row unknown
    fallback fires — a silently wrong scan estimate is the single worst
    input to join ordering."""
    key = str(path)
    if key in _warned_unknown:
        return
    _warned_unknown.add(key)
    from bodo_tpu.utils import tracing
    from bodo_tpu.utils.logging import log
    with tracing.event("stats_unknown_fallback", path=key):
        pass
    log(1, f"stats: no row count for {key}; assuming 1,000,000 rows")


def _parquet_rows(path) -> int:
    try:
        sig = _dataset_sig(path)
    except Exception:
        _note_unknown(path)
        return 1_000_000  # unknown: assume big; don't cache the guess
    hit = _pq_rows_cache.get(sig)
    if hit is not None:
        return hit
    try:
        # footers come from the shared cache — a plan whose scan already
        # read the data pays nothing here
        from bodo_tpu.io.parquet import footer_metadata
        n = sum(footer_metadata(f, sig=(f, *stamp)).num_rows
                for f, stamp in zip(sig[0], sig[1]))
    except Exception:
        _note_unknown(path)
        return 1_000_000
    _pq_rows_cache[sig] = n
    return n


def selectivity(e: Expr) -> float:
    """Textbook predicate selectivity factors (System R defaults)."""
    if isinstance(e, BinOp):
        if e.op == "&":
            return selectivity(e.left) * selectivity(e.right)
        if e.op == "|":
            sl, sr = selectivity(e.left), selectivity(e.right)
            return min(1.0, sl + sr - sl * sr)
        if e.op == "==":
            return 0.1
        if e.op in ("<", "<=", ">", ">="):
            return 0.3
        if e.op == "!=":
            return 0.9
    if isinstance(e, IsIn):
        return min(1.0, 0.1 * max(len(e.values), 1))
    if isinstance(e, StrPredicate):
        if e.kind == "eq_any":
            return min(1.0, 0.1 * max(len(e.pattern), 1))
        return 0.25
    if isinstance(e, UnOp) and e.op == "~":
        return max(0.0, 1.0 - selectivity(e.operand))
    return 0.25


def estimate(node: L.Node) -> Tuple[float, float]:
    """(estimated rows, raw underlying rows). When the adaptive layer
    has OBSERVED this subplan's cardinality (this process or the
    persistent stats store), the observation replaces the estimated
    component; the raw component keeps its structural meaning (ndv proxy
    for join_estimate) except for sources, where raw == rows."""
    if _runtime_override is not None:
        ov = _runtime_override(node)
        if ov is not None:
            est = max(float(ov), 1.0)
            if isinstance(node, (L.ReadParquet, L.ReadCsv, L.FromPandas)):
                return est, est
            return est, _estimate_impl(node)[1]
    return _estimate_impl(node)


def _estimate_impl(node: L.Node) -> Tuple[float, float]:
    if isinstance(node, L.ReadParquet):
        n = float(_parquet_rows(node.path))
        return n, n
    if isinstance(node, L.ReadCsv):
        return 100_000.0, 100_000.0  # csv has no cheap footer
    if isinstance(node, L.FromPandas):
        n = float(node.table.nrows)
        return n, n
    if isinstance(node, L.Filter):
        est, raw = estimate(node.child)
        return max(est * selectivity(node.predicate), 1.0), raw
    if isinstance(node, (L.Projection, L.Window, L.RankWindow,
                         L.AggWindow, L.Sort)):
        return estimate(node.child)
    if isinstance(node, L.Limit):
        est, raw = estimate(node.child)
        return min(float(node.n), est), raw
    if isinstance(node, (L.Aggregate, L.Distinct)):
        est, raw = estimate(node.child)
        return max(est ** 0.75, 1.0), max(est ** 0.75, 1.0)
    if isinstance(node, L.Reduce):
        return 1.0, 1.0
    if isinstance(node, L.Union):
        parts = [estimate(c) for c in node.children]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)
    if isinstance(node, L.Join):
        le, lr = estimate(node.left)
        re_, rr = estimate(node.right)
        if node.how == "cross":
            return max(le * re_, 1.0), max(lr, rr)
        ndv = join_key_ndv([(node.left, lk, node.right, rk)
                            for lk, rk in zip(node.left_on, node.right_on)])
        return join_estimate(le, lr, re_, rr, ndv), max(lr, rr)
    return 10_000.0, 10_000.0  # unknown node: neutral guess


def join_estimate(a_est: float, a_raw: float,
                  b_est: float, b_raw: float,
                  key_ndv: Optional[float] = None) -> float:
    """|A ⋈ B| ≈ |A|·|B| / ndv(key). ndv(key) is the rows of the smaller
    raw side (exact when the key is that side's primary key: the common
    FK-join shape), capped by `key_ndv`, a bound on the join keys'
    distinct values (`join_key_ndv`), where one is known: a key that
    repeats on both sides (supplier and customer on nationkey) is then
    no longer priced as if one side held each value once. The row count
    stays as the other cap because a range says nothing of a composite
    key: a pair (partkey, suppkey) spans parts x suppliers and has as
    many values as partsupp has rows. Without a bound the row count
    stands alone."""
    ndv = min(a_raw, b_raw)
    if key_ndv is not None:
        ndv = min(ndv, key_ndv)
    return max(a_est * b_est / max(ndv, 1.0), 1.0)


# ---------------------------------------------------------------------------
# distinct-value bounds of join keys
# ---------------------------------------------------------------------------

def _resident_span(table, name: str) -> Optional[int]:
    """Value span of a resident column (dictionary length for strings,
    hi - lo + 1 for integers, dates and bools), read as
    `relational._key_ranges` reads it and kept on the `Column` as
    `ndv_bound`, so a table pays the min/max reduction once in its life
    and not once a query. Not `vrange`: derived columns inherit that,
    and the dense join and groupby gates read it."""
    col = table.columns.get(name)
    if col is None:
        return None
    if col.ndv_bound is None:
        from bodo_tpu import relational as R
        from bodo_tpu.table.table import Table
        # one column under one name: columns of one shape share a program
        one = Table({"k": col}, table.nrows, table.distribution,
                    table.counts)
        (r,), _ = R._key_ranges(one, ["k"])
        col.ndv_bound = 0 if r is None else max(int(r[1] - r[0] + 1), 1)
    return col.ndv_bound or None


def _parquet_span(path, name: str) -> Optional[int]:
    """Value span of an integer or date column of a parquet dataset from
    the row-group statistics of the footers the scan already caches;
    None when a row group lacks them."""
    try:
        sig = _dataset_sig(path)
    except Exception:
        return None
    key = (sig, name)
    if key not in _pq_span_cache:
        try:
            _pq_span_cache[key] = _footer_span(sig, name)
        except Exception:  # statistics are an optimisation, as the scan's
            _pq_span_cache[key] = None
    return _pq_span_cache[key]


def _footer_span(sig, name: str) -> Optional[int]:
    import datetime

    from bodo_tpu.io.parquet import footer_metadata
    lo = hi = None
    for f, stamp in zip(sig[0], sig[1]):
        md = footer_metadata(f, sig=(f, *stamp))
        ci = md.schema.names.index(name)
        for rg in range(md.num_row_groups):
            st = md.row_group(rg).column(ci).statistics
            if st is None or not st.has_min_max:
                return None
            a, b = st.min, st.max
            if isinstance(a, datetime.date) and \
                    not isinstance(a, datetime.datetime):
                a, b = a.toordinal(), b.toordinal()
            if not isinstance(a, int):
                return None
            lo = a if lo is None else min(lo, a)
            hi = b if hi is None else max(hi, b)
    return None if lo is None else max(hi - lo + 1, 1)


def key_ndv_bound(node: L.Node, col: str) -> Optional[int]:
    """Upper bound on the distinct values of output column `col` of
    `node`, or None. Walks through Filter, Sort and Limit, through a
    Projection only where the column is a bare ColRef, and through a
    Join to the side that owns the column, down to the source: a
    resident column's value span (`_resident_span`) or a parquet
    column's footer statistics. The bound is of the source, so a filter
    below does not change it and the static and the run-time pass read
    the same number whatever has been observed. Float, datetime and
    computed keys give None."""
    while True:
        if isinstance(node, (L.Filter, L.Sort, L.Limit)):
            node = node.child
        elif isinstance(node, L.Projection):
            e = next((e for n, e in node.exprs if n == col), None)
            if not isinstance(e, ColRef):
                return None
            node, col = node.child, e.name
        elif isinstance(node, L.Join):
            # an equal-name key of an inner join carries the left side's
            # values; a suffixed name ends at a source that lacks it
            both = col in node.left.schema and col in node.right.schema
            if both and node.how != "inner":
                return None
            node = node.left if col in node.left.schema else node.right
        elif isinstance(node, L.FromPandas):
            return _resident_span(node.table, col)
        elif isinstance(node, L.ReadParquet):
            if col not in node.schema or \
                    node.schema[col].kind not in ("i", "u", "date"):
                return None
            return _parquet_span(node.path, col)
        else:
            return None


def join_key_ndv(pairs: Sequence[Tuple[L.Node, str, L.Node, str]]
                 ) -> Optional[float]:
    """Bound on the distinct values of a join's key: the product over
    the key columns `(left node, left column, right node, right column)`
    of the larger of the two sides' bounds. None when some column has no
    bound on either side (`join_estimate` then divides by the row count
    alone, as it always did)."""
    out = 1.0
    for ln, lc, rn, rc in pairs:
        lb, rb = key_ndv_bound(ln, lc), key_ndv_bound(rn, rc)
        if lb is None or rb is None:
            return None
        out *= max(lb, rb)
    return out


# ---------------------------------------------------------------------------
# the greedy join order
# ---------------------------------------------------------------------------

def greedy_join_order(rels: Sequence[L.Node], edges: Sequence[Tuple]
                      ) -> Tuple[int, List[Tuple]]:
    """Greedy cost-based order of an inner equi-join graph (replaces the
    reference's vendored DuckDB join-order optimizer, bodo/pandas/plan.py
    get_plan_cardinality). `rels` are the relations' plans, `edges`
    `(rel_i, rel_j, key_i, key_j)` equalities between two of them.
    Starts from the smallest-estimate relation that has an edge, then
    repeatedly joins the connected relation whose estimated output
    (`join_estimate` on the keys' bounds) is smallest. Returns
    `(start, steps)`, one step a relation joined:
    `(rel, left keys, right keys, edge ids)`; where nothing connects,
    the step is the smallest remaining relation with no keys, and the
    caller cross-joins or gives up."""
    ests = [estimate(r) for r in rels]
    # an edge's bound does not depend on which side is joined first
    edge_ndv = [join_key_ndv([(rels[ri], fi, rels[rj], fj)])
                for ri, rj, fi, fj in edges]
    has_edge = {r for e in edges for r in (e[0], e[1])}
    start = min(range(len(rels)),
                key=lambda i: (i not in has_edge, ests[i][0]))
    used = {start}
    cur_est, cur_raw = ests[start]
    consumed: Set[int] = set()
    steps: List[Tuple] = []
    while len(used) < len(rels):
        best = None
        for i in range(len(rels)):
            if i in used:
                continue
            kl, kr, ids = [], [], []
            for eid, (ri, rj, fi, fj) in enumerate(edges):
                if eid in consumed:
                    continue
                if rj in used and ri == i:
                    fi, fj = fj, fi
                elif not (ri in used and rj == i):
                    continue
                kl.append(fi)
                kr.append(fj)
                ids.append(eid)
            if kl:
                ndvs = [edge_ndv[e] for e in ids]
                ndv = None if None in ndvs else math.prod(ndvs)
                _priced["unkeyed" if ndv is None else "keyed"] += 1
                out = join_estimate(cur_est, cur_raw, *ests[i], ndv)
                if best is None or out < best[0]:
                    best = (out, i, kl, kr, ids)
        if best is None:
            # disconnected: the smallest remainder, no keys
            i = min((j for j in range(len(rels)) if j not in used),
                    key=lambda j: ests[j][0])
            best = (cur_est * max(ests[i][0], 1.0), i, [], [], [])
        cur_est, i, kl, kr, ids = best
        cur_raw = max(cur_raw, ests[i][1])
        used.add(i)
        consumed.update(ids)
        steps.append((i, kl, kr, ids))
    return start, steps
