"""Plan executor: logical plan → relational-layer calls → Table.

Analogue of the reference's physical conversion + pipeline executor
(bodo/pandas/_physical_conv.h:29 PhysicalPlanBuilder,
bodo/pandas/_executor.h:76 Executor). The streaming C++ pipelines become
a post-order walk issuing cached jitted stages; results memoize on the
node (plan collapse) and in a session-level cache keyed by plan identity.
"""

from __future__ import annotations

import threading
import time as _time

import numpy as np

from bodo_tpu import relational as R
from bodo_tpu.config import config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan import logical as L
from bodo_tpu.plan.optimizer import optimize
from bodo_tpu.runtime import resilience, result_cache as _rcache
from bodo_tpu.table.table import ONED, REP, Table
from bodo_tpu.utils import tracing
from bodo_tpu.utils.logging import log

# session-level semantic result cache (runtime/result_cache.py): entries
# key on (plan fingerprint, environment, dataset signatures) so a
# changed source file never serves a stale result. The old name stays
# bound for its dict-shaped call sites (.clear() in tests,
# .pop(raw_key) after fusion's buffer donation).
_result_cache = _rcache.cache()

# graceful-degradation state for the executing thread: while a stage is
# being re-run replicated, _maybe_shard must not re-shard its sources
_degrade_tls = threading.local()


def execute(node: L.Node, optimize_first: bool = True) -> Table:
    with tracing.event("query"):
        return _execute(node, optimize_first)


def _execute(node: L.Node, optimize_first: bool) -> Table:
    if optimize_first:
        with tracing.event("plan.optimize"):
            node = optimize(node)
    if config.plan_validate:
        # shardcheck layer 1: reject ill-typed plans (distribution /
        # schema invariant violations) before any kernel traces or
        # collectives dispatch — PlanInvariantError in milliseconds
        # instead of wrong answers or a wedged gang
        from bodo_tpu.analysis.plan_validator import validate_plan
        with tracing.event("plan.validate"):
            validate_plan(node)
    # whole-stage fusion planning: annotate maximal pipeline-compatible
    # regions (filter/project chains + dense-agg roots) so _exec_inner
    # dispatches each as ONE compiled program. Planning is best-effort —
    # a failure here must cost per-node execution, never the query.
    try:
        from bodo_tpu.plan.fusion import plan_fusion_groups
        with tracing.event("plan.fusion"):
            plan_fusion_groups(node)
    except Exception as e:  # noqa: BLE001 - fusion is an optimization
        log(1, f"fusion planning failed, executing unfused: {e}")
    if not tracing.is_tracing():
        return _rcache.cached_execute(node, _exec)
    # every traced execution belongs to a query: adopt the caller's
    # span if one is active, otherwise open one for this plan so all
    # events/records below carry a query id. The serving layer's
    # session (if any) tags the query — EXPLAIN/slow-query records then
    # say WHICH tenant ran the plan (multi-tenant attribution)
    from bodo_tpu.plan import explain
    session = _current_session()
    qid = tracing.current_query_id()
    if qid is not None:
        explain.begin_query(node, qid, session=session)
        return _rcache.cached_execute(node, _exec)
    with tracing.query_span() as qid:
        explain.begin_query(node, qid, session=session)
        return _rcache.cached_execute(node, _exec)


def _current_session():
    """Serving-session id of the executing query, or None outside the
    serving layer (lazy: never imports the scheduler)."""
    import sys
    sch = sys.modules.get("bodo_tpu.runtime.scheduler")
    if sch is None:
        return None
    try:
        return sch.current_session()
    except Exception:  # noqa: BLE001 - attribution is best-effort
        return None


def _maybe_shard(t: Table) -> Table:
    """Scan distribution policy: shard large sources over the mesh; keep
    small ones replicated so joins against them broadcast instead of
    shuffling (the reference's broadcast-join size heuristic)."""
    return t.shard() if _shards_source(t) else t


def _shards_source(t: Table) -> bool:
    return t.distribution != ONED and \
        not getattr(_degrade_tls, "force_rep", False) and \
        t.nrows >= config.shard_min_rows and mesh_mod.num_shards() > 1


def _place_source(node: L.FromPandas) -> Table:
    """`_maybe_shard` for a registered table, scattered once: the
    columns sharded for a mesh are kept on the node the table was
    registered under (`FromPandas.source`), beside the replicated
    column each came from, and a copy cut to a query's columns reads
    them there and shards only what no query has read yet. The
    replicated original stays where registration put it (one copy, on
    the default device): the planner's statistics, a degraded re-run
    and another mesh read it."""
    t = node.table
    if not _shards_source(t):
        return t
    held = node.source.placed
    m = mesh_mod.get_mesh()
    if held.get("mesh") != m:
        held.update(mesh=m, columns={}, counts=None)
    cols = held["columns"]
    missing = [n for n, c in t.columns.items()
               if n not in cols or cols[n][0] is not c]
    if missing:
        s = t.select(missing).shard()
        cols.update({n: (t.columns[n], s.columns[n]) for n in missing})
        held["counts"] = s.counts
    return Table({n: cols[n][1] for n in t.names}, t.nrows, ONED,
                 held["counts"])


def _exec(node: L.Node) -> Table:
    """Execute one node (children first, inside its span). The
    executor's own bookkeeping around it is the span `exec.observe`,
    once before the node (result-cache lookup, EXPLAIN snapshots) and
    once after (EXPLAIN record, AQE and elastic observations, result-
    cache record)."""
    traced = tracing.is_tracing()
    with tracing.event("exec.observe"):
        if node._cached is not None:
            if traced:
                _record_node(node, node._cached, 0.0, cached=True)
            return node._cached
        key = _rcache.node_key(node)
        hit = _rcache.lookup(key)
        if hit is not None:
            node._cached = hit
            if traced:
                _record_node(node, hit, 0.0, cached=True)
            return hit
        before = _snapshot_node(node) if traced else {}
    span_args = {}
    path = getattr(node, "_explain_path", None)
    if path is not None:
        span_args["path"] = path
    t0 = _time.perf_counter()
    with tracing.event(type(node).__name__, **span_args) as ev:
        t = _exec_with_oom_retry(node)
        if ev is not None:
            ev["rows"] = t.nrows
    wall_s = _time.perf_counter() - t0
    with tracing.event("exec.observe"):
        if traced:
            _record_node(node, t, wall_s, **before)
        node._cached = t
        # stage-boundary statistics feedback; a stage that came back
        # from a degraded replicated re-run is tainted (execution
        # artifact, not a data property) and must not feed the stats
        # store
        if getattr(_degrade_tls, "tainted", False):
            _degrade_tls.tainted = False
        else:
            from bodo_tpu.plan import adaptive
            adaptive.observe_stage(node, t)
        _rcache.record(key, node.key(), t, wall_s)
        try:
            # elastic checkpoint anchor: the AQE observation point
            # doubles as the resumable-suffix boundary (the result cache
            # owns the bytes; elastic tracks registration + accounting
            # for /healthz)
            from bodo_tpu.runtime import elastic
            elastic.observe_stage(node, wall_s)
        except Exception:  # noqa: BLE001 - accounting never fails a query
            pass
    return t


def _snapshot_node(node: L.Node) -> dict:
    """What `_record_node` subtracts from: the pre-execution estimate,
    AQE decision counters (EXPLAIN ANALYZE annotations: est-vs-actual
    and which adaptive decisions this node triggered), the comm and
    compile observatories' totals and the host syncs so far."""
    before = {"syncs_before": sum(tracing.sync_counts().values())}
    try:
        from bodo_tpu.plan import adaptive, stats
        before["est_rows"] = stats.estimate(node)[0]
        before["aqe_before"] = dict(adaptive.stats().get("decisions", {}))
    except Exception:  # noqa: BLE001 - annotation is best-effort
        pass
    try:
        # comm-observatory snapshot: the delta across the node's span
        # is its inclusive comm-wait vs compute split
        from bodo_tpu.parallel import comm
        before["comm_before"] = comm.stats()
    except Exception:  # noqa: BLE001
        pass
    try:
        # observatory snapshot: compiles/retraces/device bytes that land
        # during this node's span are attributed to it
        from bodo_tpu.runtime import xla_observatory
        before["xla_before"] = xla_observatory.head()
    except Exception:  # noqa: BLE001
        pass
    return before


def _record_node(node: L.Node, t: Table, wall_s: float,
                 cached: bool = False, est_rows=None,
                 aqe_before=None, comm_before=None,
                 xla_before=None, syncs_before=None) -> None:
    """EXPLAIN ANALYZE observation for one executed (or cache-hit) node:
    rows, result device bytes, inclusive wall, the delta of AQE
    decision counters and of the comm-observatory totals across the
    node's execution, and in `xla` beside its dispatches its host syncs
    (`tracing.host_read` calls, inclusive like the wall): how often the
    operator stopped the device to read a value back. Best-effort — an
    annotation failure never fails the query."""
    try:
        from bodo_tpu.plan import explain
        aqe_delta = None
        if aqe_before is not None:
            from bodo_tpu.plan import adaptive
            after = adaptive.stats().get("decisions", {})
            aqe_delta = {k: v - aqe_before.get(k, 0)
                         for k, v in after.items()
                         if v - aqe_before.get(k, 0)}
        comm_delta = None
        if comm_before is not None:
            try:
                from bodo_tpu.parallel import comm
                after_c = comm.stats()
                d = {
                    "wall_s": after_c["wall_s"] - comm_before["wall_s"],
                    "wait_s": after_c["wait_s"] - comm_before["wait_s"],
                    "bytes": (after_c["bytes_out"] + after_c["bytes_in"]
                              - comm_before["bytes_out"]
                              - comm_before["bytes_in"]),
                }
                if d["bytes"] or d["wall_s"] > 1e-9 \
                        or d["wait_s"] > 1e-9:
                    comm_delta = d
            except Exception:  # noqa: BLE001
                pass
        xla_delta = None
        if xla_before is not None:
            try:
                from bodo_tpu.runtime import xla_observatory
                after_x = xla_observatory.head()
                compiles = after_x["compiles"] - xla_before["compiles"]
                retraces = after_x["retraces"] - xla_before["retraces"]
                disp = after_x["dispatches"] - xla_before["dispatches"]
                dev = after_x["live_bytes"] - xla_before["live_bytes"]
                syncs = 0 if syncs_before is None else \
                    sum(tracing.sync_counts().values()) - syncs_before
                if compiles or retraces or disp or dev or syncs:
                    xla_delta = {"compiles": compiles,
                                 "retraces": retraces,
                                 "dispatches": disp,
                                 "syncs": syncs,
                                 "dev_bytes": dev}
                    if retraces:
                        xla_delta["cause"] = after_x["last_cause"]
            except Exception:  # noqa: BLE001
                pass
        nbytes = None
        try:
            from bodo_tpu.runtime.memory_governor import \
                table_device_bytes
            nbytes = int(table_device_bytes(t))
        except Exception:  # noqa: BLE001
            pass
        explain.record(node, rows=t.nrows, wall_s=wall_s,
                       est_rows=est_rows, bytes=nbytes, cached=cached,
                       aqe=aqe_delta, comm=comm_delta,
                       fusion=getattr(node, "_fusion_info", None),
                       xla=xla_delta)
    except Exception:  # noqa: BLE001 - observability must not break exec
        pass


_MAX_OOM_RETRIES = 3


def _exec_with_oom_retry(node: L.Node) -> Table:
    """Stage-boundary recovery envelope, two legs:

    OOM retry — XLA RESOURCE_EXHAUSTED from a stage turns into (halve
    the fattest operator grant, spill parked state via the comptroller,
    re-run the stage) instead of a hard crash. Safe to re-run: child
    results are memoized on their nodes, so only the failed stage
    recomputes — under the shrunken grant it takes its partitioned/
    spill path.

    Graceful degradation — a sharded collective failing with a non-OOM
    internal error (or an armed `collective` fault) re-executes the
    stage replicated: materialized 1D inputs are gathered, sources stay
    REP for the re-run, and the REP kernel paths need no collectives."""
    from bodo_tpu.runtime.memory_governor import governor
    last = None
    for attempt in range(_MAX_OOM_RETRIES + 1):
        try:
            return _exec_inner(node)
        except Exception as e:  # noqa: BLE001 - classified below
            gov = governor()
            if (not config.mem_governor or not gov.is_oom(e)
                    or attempt == _MAX_OOM_RETRIES):
                out = _try_degrade(node, e)
                if out is not None:
                    return out
                raise
            last = e
            with tracing.event("oom_retry", stage=type(node).__name__,
                               attempt=attempt + 1):
                if not gov.handle_oom(e):
                    raise
            log(1, f"OOM at {type(node).__name__} (attempt "
                   f"{attempt + 1}): grant halved, parked state "
                   f"spilled, re-running stage")
    raise last  # pragma: no cover - loop always returns or raises


def _try_degrade(node: L.Node, err: Exception):
    """Re-execute a stage replicated after a sharded-collective failure.

    Returns the replicated result, or None when degradation does not
    apply (disabled, error not collective-shaped, already inside a
    degraded re-run) or when the replicated re-run itself fails — the
    caller then raises the ORIGINAL error. The innermost failing stage
    degrades first; its replicated result feeds parent stages normally."""
    if not config.degrade_replicated or \
            getattr(_degrade_tls, "force_rep", False):
        return None
    if not resilience.is_degradable(err):
        return None
    stage = type(node).__name__
    # pull this stage's materialized 1D inputs back to one replicated
    # copy; un-materialized children re-execute under force_rep below.
    # Snapshot the originals so a failed re-run leaves the plan's cached
    # distributions untouched for any later re-execution.
    snapshot = [(c, c._cached) for c in node.children]
    for c in node.children:
        if c._cached is not None and c._cached.distribution == ONED:
            c._cached = c._cached.gather()
    _degrade_tls.force_rep = True
    try:
        with tracing.event("degrade_replicated", stage=stage):
            out = _exec_inner(node)
    except Exception:  # noqa: BLE001 - degraded re-run failed too
        for c, cached in snapshot:
            c._cached = cached
        return None
    finally:
        _degrade_tls.force_rep = False
    resilience.count_degradation(stage)
    _degrade_tls.tainted = True
    log(1, f"collective failure at {stage}: re-executed replicated "
           f"({type(err).__name__})")
    return out


def apply_projection(t: Table, exprs) -> Table:
    """Evaluate a Projection node's exprs on a table (shared with the
    streaming executor's per-batch project stage)."""
    from bodo_tpu.plan.expr import ColRef
    new = {}
    names = []
    for n, e in exprs:
        names.append(n)
        if not (isinstance(e, ColRef) and e.name == n):
            new[n] = e
    t = R.assign_columns(t, new) if new else t
    return t.select(names)


def _exec_inner(node: L.Node) -> Table:
    resilience.maybe_inject("stage.boundary")
    if config.stream_exec and isinstance(node, (L.Aggregate, L.Reduce,
                                                L.Sort)):
        from bodo_tpu.plan import streaming
        out = streaming.try_stream_execute(node)
        if out is not None:
            return out
    # whole-stage fusion: a group root dispatches its whole region as
    # one compiled program. Streaming wins for memory-bounded aggregates
    # (above) — its per-batch chains fuse internally via stream_chain.
    # A None return (unfusable at runtime) falls through to per-node.
    group = getattr(node, "_fusion_group", None)
    if group is not None:
        from bodo_tpu.plan import fusion
        if isinstance(group, fusion.FusionGroup):
            out = fusion.execute_group(group, _exec)
        else:
            from bodo_tpu.plan import fusion_join
            out = fusion_join.execute_join_group(group, _exec)
        if out is not None:
            return out
    if isinstance(node, L.ReadParquet):
        from bodo_tpu.io import read_parquet
        from bodo_tpu.io.parquet import dataset_nbytes
        from bodo_tpu.runtime.memory_governor import reserve
        log(1, f"read_parquet({node.path}) columns={node.columns}")
        # admission-control the materializing scan against the derived
        # budget (on-disk bytes as the want estimate; 0 = unknown, skip)
        nbytes = dataset_nbytes(node.path)
        if nbytes > 0:
            with reserve("read_parquet", nbytes):
                return _maybe_shard(
                    read_parquet(node.path, columns=node.columns))
        return _maybe_shard(read_parquet(node.path, columns=node.columns))
    if isinstance(node, L.ReadCsv):
        from bodo_tpu.io import read_csv
        return _maybe_shard(read_csv(
            node.path, columns=node.columns,
            parse_dates=list(node.parse_dates) or None))
    if isinstance(node, L.FromPandas):
        return _place_source(node)
    if isinstance(node, L.ViewScan):
        from bodo_tpu.runtime import views as _views
        return _maybe_shard(_views.materialized_table(node.name))
    if isinstance(node, L.Projection):
        return apply_projection(_exec(node.child), node.exprs)
    if isinstance(node, L.Filter):
        return R.filter_table(_exec(node.child), node.predicate)
    if isinstance(node, L.Aggregate):
        return R.groupby_agg(_exec(node.child), node.keys, node.aggs)
    if isinstance(node, L.Reduce):
        scalars = R.reduce_table(_exec(node.child), node.aggs)
        import pandas as pd
        df = pd.DataFrame({k: [v] for k, v in scalars.items()})
        return Table.from_pandas(df)
    if isinstance(node, L.Join):
        from bodo_tpu.plan import adaptive
        repl = adaptive.maybe_reoptimize_join(node, _exec)
        if repl is not None:
            # observed leaf cardinalities changed the join order:
            # execute the re-planned subtree (leaf results are memoized,
            # so only the joins themselves run). The rewrite must
            # preserve the original subtree's schema and abstract
            # distribution — validated before anything executes.
            if config.plan_validate:
                from bodo_tpu.analysis.plan_validator import \
                    validate_rewrite
                validate_rewrite(node, repl)
            if tracing.is_tracing():
                # re-anchor the substituted subtree's EXPLAIN paths
                # under the join it replaced, flagged as replanned
                from bodo_tpu.plan import explain
                explain.assign_paths(
                    repl, getattr(node, "_explain_path", None) or "0",
                    force=True, replanned=True)
            return _exec(repl)
        left = _exec(node.left)
        right = _exec(node.right)
        return R.join_tables(left, right, node.left_on, node.right_on,
                             node.how, node.suffixes,
                             null_equal=node.null_equal)
    if isinstance(node, L.NonEquiJoin):
        from bodo_tpu.ops import nonequi
        left = _exec(node.left).gather()
        right = _exec(node.right).gather()
        iv = nonequi.match_interval_pattern(
            node.pred, set(node.left.schema), set(node.right.schema))
        if iv is not None:
            out = nonequi.nl_join_interval(left, right, node.pred,
                                           iv[0], iv[1], node.how)
        else:
            out = nonequi.nl_join_rep(left, right, node.pred, node.how)
        return _maybe_shard(out)
    if isinstance(node, L.Explode):
        from bodo_tpu.table import nested as _nested
        out = _nested.flatten_table(_exec(node.child), node.column,
                                    node.value_name, node.index_name,
                                    node.outer)
        return _maybe_shard(out)
    if isinstance(node, L.Union):
        return _maybe_shard(R.concat_tables(
            [_exec(c) for c in node.children]))
    if isinstance(node, L.Window):
        return R.window_table(_exec(node.child), node.specs)
    if isinstance(node, L.RankWindow):
        return R.rank_window(_exec(node.child), node.partition_by,
                             node.order_by, node.specs, node.ascending)
    if isinstance(node, L.AggWindow):
        return R.agg_window(_exec(node.child), node.partition_by,
                            node.order_by, node.specs, node.ascending)
    if isinstance(node, L.Sort):
        return R.sort_table(_exec(node.child), node.by, node.ascending,
                            node.na_last)
    if isinstance(node, L.Limit):
        return R.head_table(_exec(node.child), node.n)
    if isinstance(node, L.Distinct):
        child = _exec(node.child)
        others = [n for n in child.names if n not in node.subset]
        aggs = [(n, "first", n) for n in others]
        out = R.groupby_agg(child, node.subset, aggs)
        return out.select(child.names)
    raise TypeError(f"cannot execute {node!r}")
