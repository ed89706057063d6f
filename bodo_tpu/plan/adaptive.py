"""Adaptive query execution: runtime statistics feedback.

The optimizer plans from plan/stats.py textbook estimates; this module
closes the loop at stage boundaries, where actual cardinalities are free
(every materialized stage already knows its row counts):

  * observation — plan/physical._exec and both streaming executors
    report each completed stage's rows/bytes here. The per-stage q-error
    (max(est/actual, actual/est)) feeds the tracing profile,
    observed rows override ``stats.estimate()`` for every subplan
    not yet executed, and fingerprint-stable subplans persist to the
    stats store (runtime/stats_store.py) for future processes.
  * broadcast promote/demote — the broadcast-vs-shuffle join decision in
    relational.join_tables re-evaluates against the memory governor's
    derived budget: a build side whose OBSERVED bytes fit
    aqe_bcast_frac x budget broadcasts even when the rows heuristic
    planned a full shuffle, and an oversized planned broadcast demotes
    to a shuffle join (the reference decides this statically at plan
    time; on TPU the all_to_all is expensive enough that the runtime
    correction pays for itself).
  * skew splits — before a sharded join pays an all_to_all, the probe
    key distribution is sampled; hot keys above aqe_skew_frac split off
    and broadcast-join against their (small) build subset so the shuffle
    carries only the cold remainder.
  * batch coalescing — undersized streaming batches (post-filter) merge
    until they reach AQE_COALESCE_FRAC of the nominal batch size, so
    per-batch kernels don't run near-empty.
  * mid-plan re-optimization — inner-join chains re-run
    ``optimizer.reorder_joins`` once their leaf relations have observed
    cardinalities; a changed order re-plans the not-yet-executed joins
    (leaf results stay memoized on their nodes, so nothing re-executes).

Degraded replicated re-runs (runtime/resilience.py) are execution-path
artifacts, not data properties — observation is suspended while one is
in flight so they cannot poison the stats store.

Default-on via ``set_config(aqe=...)`` / ``BODO_TPU_AQE``; every
decision lands in an ``aqe:*`` counter (tracing.profile / dump).
"""

from __future__ import annotations

import threading
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from bodo_tpu.config import config

_lock = threading.Lock()
_counters: Dict[str, int] = defaultdict(int)
_observed: Dict[tuple, float] = {}
_qerr: List[dict] = []
_MAX_QERR = 512
_MAX_OBSERVED = 4096
_injector = None  # test hook: fn(node) -> Optional[rows]


def enabled() -> bool:
    return bool(config.aqe)


def _suspended() -> bool:
    """True while a degraded replicated re-run is in flight — its
    execution shape is an artifact of the failure, not of the data."""
    from bodo_tpu.plan import physical
    return bool(getattr(physical._degrade_tls, "force_rep", False))


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] += n


def reset() -> None:
    """Clear decisions / q-errors / in-process observations (tests)."""
    with _lock:
        _counters.clear()
        _qerr.clear()
        _observed.clear()
        _stats_mod._priced.update(keyed=0, unkeyed=0)


def set_estimate_injector(fn) -> None:
    """Test hook: ``fn(node) -> Optional[rows]`` forces mis-estimates so
    tests can assert each adaptive correction actually triggers. None
    uninstalls."""
    global _injector
    with _lock:
        _injector = fn


# ---------------------------------------------------------------------------
# stats.estimate() override (observed > injected > persisted)
# ---------------------------------------------------------------------------

def estimate_override(node) -> Optional[float]:
    """Installed as plan.stats._runtime_override: returns observed rows
    for a subplan, or None to keep the structural estimate."""
    if not enabled():
        return None
    try:
        key = node.key()
    except Exception:
        return None
    with _lock:
        got = _observed.get(key)
    if got is not None:
        return got
    if _injector is not None:
        inj = _injector(node)
        if inj is not None:
            return float(inj)
    try:
        from bodo_tpu.runtime import stats_store
        return stats_store.get_store().lookup(stats_store.fingerprint(node))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# stage-boundary observation
# ---------------------------------------------------------------------------

def observe_stage(node, table) -> None:
    """Record a completed stage's actual cardinality (called from
    plan/physical._exec after each stage materializes). First
    observation of a plan key also records its q-error against the
    estimate the planner would have used."""
    if not enabled() or _suspended():
        return
    try:
        actual = int(table.nrows)
        from bodo_tpu.plan import stats as stats_mod
        est, _ = stats_mod.estimate(node)
        key = node.key()
    except Exception:
        return
    with _lock:
        first = key not in _observed
        if first and len(_observed) >= _MAX_OBSERVED:
            _observed.clear()  # unbounded plans: drop, don't leak
        _observed[key] = float(actual)
        if first and len(_qerr) < _MAX_QERR:
            q = max(max(est, 1.0) / max(actual, 1.0),
                    max(actual, 1.0) / max(est, 1.0))
            _qerr.append({"stage": type(node).__name__,
                          "est": float(est), "actual": actual,
                          "q": float(q)})
    try:
        from bodo_tpu.runtime import stats_store
        from bodo_tpu.runtime.memory_governor import table_device_bytes
        stats_store.get_store().record(
            stats_store.fingerprint(node), actual,
            table_device_bytes(table))
    except Exception:
        pass


def observe_batch(table) -> None:
    """Streaming executors report every pushed batch (fill statistics
    show up as aqe:stream:* counters)."""
    if not enabled():
        return
    with _lock:
        _counters["stream:batches"] += 1
        _counters["stream:rows"] += int(table.nrows)


def observe_shuffle(t, key_cols) -> None:
    """Sample a shuffle's key distribution (the per-key skew sketch at
    the all_to_all boundary); a dominant key bumps aqe:skew:detected."""
    if not enabled() or _suspended():
        return
    if t.nrows < max(config.aqe_skew_min_rows, 1) or len(key_cols) != 1:
        return
    try:
        c = t.column(key_cols[0])
        if c.dictionary is None and \
                np.dtype(c.dtype.numpy).kind not in "iu":
            return
        vals, n = _sample_key(t, key_cols[0], 4096)
        if n == 0:
            return
        _, cnts = np.unique(vals, return_counts=True)
        if float(cnts.max()) / float(n) >= config.aqe_skew_frac:
            count("skew:detected")
    except Exception:
        return


def _sample_key(t, name: str, m: int) -> Tuple[np.ndarray, int]:
    """Host sample of a 1D table's key column: a prefix slice per shard
    (biased only when rows are key-sorted — fine for a sketch). Returns
    (non-null sampled values, total sampled rows incl. nulls)."""
    import jax
    c = t.column(name)
    per = t.shard_capacity
    take = max(m // max(t.num_shards, 1), 32)
    datas, valids = [], []
    total = 0
    for s in range(t.num_shards):
        n = min(int(t.counts[s]), take)
        if n <= 0:
            continue
        sl = slice(s * per, s * per + n)
        datas.append(np.asarray(jax.device_get(c.data[sl])))
        if c.valid is not None:
            valids.append(np.asarray(jax.device_get(c.valid[sl])))
        total += n
    if not datas:
        return np.empty(0), 0
    d = np.concatenate(datas)
    if c.valid is not None:
        d = d[np.concatenate(valids)]
    return d, total


# ---------------------------------------------------------------------------
# broadcast promote / demote
# ---------------------------------------------------------------------------

def _budget() -> int:
    if not config.mem_governor:
        return 0
    try:
        from bodo_tpu.runtime.memory_governor import governor
        return int(governor().derived_budget())
    except Exception:
        return 0


def _table_bytes(t) -> int:
    try:
        from bodo_tpu.runtime.memory_governor import table_device_bytes
        return int(table_device_bytes(t))
    except Exception:
        return 0


def join_broadcast_decision(build, probe) -> bool:
    """The broadcast-vs-shuffle gate for a 1D-both join (True = gather
    the build side, skipping both shuffles). With AQE off this is the
    legacy rows-only heuristic; with AQE on the observed build BYTES
    are checked against the governor budget, promoting large-but-narrow
    builds past the rows threshold and demoting wide ones under it."""
    static = (build.nrows <= config.bcast_join_threshold
              and probe.nrows > 4 * build.nrows)
    if not enabled() or _suspended():
        return static
    if probe.nrows <= 4 * build.nrows:
        return False  # probe too small for a broadcast to pay off
    budget = _budget()
    if budget <= 0:
        return static
    fits = _table_bytes(build) <= config.aqe_bcast_frac * budget
    if fits and not static:
        count("join:promote_broadcast")
    elif static and not fits:
        count("join:demote_broadcast")
    return fits


def should_demote_broadcast(build) -> bool:
    """A REPLICATED build side planned for a broadcast join whose
    observed bytes blow the budget: shard it (shuffle join) instead of
    keeping a full copy per device."""
    if not enabled() or _suspended():
        return False
    budget = _budget()
    if budget <= 0:
        return False
    from bodo_tpu.parallel import mesh as mesh_mod
    if mesh_mod.num_shards() <= 1 or \
            build.nrows < mesh_mod.num_shards():
        return False
    if _table_bytes(build) <= config.aqe_bcast_frac * budget:
        return False
    count("join:demote_broadcast")
    return True


# ---------------------------------------------------------------------------
# hot-key split before the join shuffle
# ---------------------------------------------------------------------------

def try_skew_split_join(left, right, left_on, right_on, how, suffixes,
                        null_equal: bool):
    """Break shuffle skew: sample the probe's join key; rows carrying a
    hot key (>= aqe_skew_frac of the sample) split off and broadcast-
    join against the hot subset of the build side, while the cold
    remainder takes the normal shuffle join. The two halves append
    shard-wise (every probe row lands in exactly one half, so inner/left
    semantics — including null and unmatched keys, which stay cold —
    are preserved). Returns the joined Table or None (not applicable)."""
    if not enabled() or _suspended():
        return None
    if how not in ("inner", "left") or len(left_on) != 1:
        return None
    if left.nrows < max(config.aqe_skew_min_rows, 1):
        return None
    from bodo_tpu.parallel import mesh as mesh_mod
    if mesh_mod.num_shards() <= 1:
        return None
    lk, rk = left_on[0], right_on[0]
    try:
        c = left.column(lk)
        # integer-typed, null-free probe keys only: the hot/cold Expr
        # masks have no Kleene-logic form, so a nullable key would drop
        # its null rows from BOTH halves
        if c.valid is not None or c.dictionary is not None or \
                np.dtype(c.dtype.numpy).kind not in "iu":
            return None
        vals, n = _sample_key(left, lk, 8192)
        if n == 0:
            return None
        uniq, cnts = np.unique(vals, return_counts=True)
        hot = uniq[cnts.astype(np.float64) / n >= config.aqe_skew_frac]
    except Exception:
        return None
    if hot.size == 0 or hot.size > 4:
        return None
    count("skew:detected")

    from bodo_tpu import relational as R
    from bodo_tpu.plan.expr import ColRef, IsIn, UnOp
    hotvals = tuple(np.asarray(hot).tolist())
    hot_pred = IsIn(ColRef(lk), hotvals)
    right_hot = R.filter_table(right, IsIn(ColRef(rk), hotvals))
    if right_hot.nrows > config.bcast_join_threshold:
        count("skew:bailed")  # build itself is hot: broadcast too big
        return None
    left_hot = R.filter_table(left, hot_pred)
    if left_hot.nrows == 0:
        return None  # sample found heat the full data doesn't have
    left_cold = R.filter_table(left, UnOp("~", hot_pred))
    count("skew:split_join")
    hot_out = R.join_tables(left_hot, right_hot.gather(), left_on,
                            right_on, how, suffixes,
                            null_equal=null_equal)
    if left_cold.nrows == 0:
        return hot_out
    with R.join_route("sort", len(left_on), left_cold.nrows, right.nrows):
        cold_out = R._join_sharded(left_cold, right, left_on, right_on, how,
                                   suffixes, null_equal=null_equal)
    return _append_splits(hot_out, cold_out)


def _append_splits(a, b):
    """Union the hot/cold join halves, shard-wise when possible."""
    from bodo_tpu import relational as R
    from bodo_tpu.table.table import ONED
    if set(a.names) == set(b.names) and a.names != b.names:
        b = b.select(a.names)
    if a.distribution == ONED and b.distribution == ONED:
        try:
            from bodo_tpu.plan.streaming_sharded import (
                _dicts_compatible, append_sharded)
            if _dicts_compatible(a, b):
                return append_sharded(a, b)
        except Exception:
            pass
    return R.concat_tables([a, b])


# ---------------------------------------------------------------------------
# streaming-batch coalescing
# ---------------------------------------------------------------------------

# Streaming batches filled below this fraction of the nominal batch size
# merge with their successors before the next per-batch kernel.
AQE_COALESCE_FRAC = 0.25


def coalesce_batches(src, sharded: bool):
    """Merge consecutive undersized streaming batches (post-filter) so
    downstream per-batch kernels see reasonably full batches instead of
    a long tail of near-empty ones. Order-preserving; an unmergeable
    pair (dict drift, schema drift) flushes and starts over."""
    if not enabled():
        yield from src
        return
    target = max(int(config.streaming_batch_size * AQE_COALESCE_FRAC), 1)
    pend = None
    for b in src:
        if pend is not None:
            merged = _merge_batches(pend, b, sharded)
            if merged is None:
                yield pend
                pend = None
            else:
                count("stream:coalesced")
                pend = merged
                if pend.nrows >= target:
                    yield pend
                    pend = None
                continue
        if b.nrows >= target:
            yield b
        else:
            pend = b
    if pend is not None:
        yield pend


def _merge_batches(a, b, sharded: bool):
    if a.names != b.names:
        return None
    try:
        if sharded:
            from bodo_tpu.plan.streaming_sharded import (
                _dicts_compatible, append_sharded)
            from bodo_tpu.table.table import ONED
            if a.distribution != ONED or b.distribution != ONED or \
                    not _dicts_compatible(a, b):
                return None
            return append_sharded(a, b)
        from bodo_tpu import relational as R
        return R.concat_tables([a, b])
    except Exception:
        return None


# ---------------------------------------------------------------------------
# mid-plan re-optimization
# ---------------------------------------------------------------------------

def maybe_reoptimize_join(node, exec_cb):
    """Re-run the greedy join ordering once the chain's leaf relations
    have OBSERVED cardinalities: the leaves execute first (they are
    needed under any order, and their results memoize on the nodes),
    then ``optimizer.reorder_joins`` re-plans with observations
    overriding the estimates. Returns the replacement subplan when the
    order changed, else None."""
    if not enabled() or _suspended():
        return None
    if getattr(node, "_aqe_reopt", False):
        return None
    node._aqe_reopt = True
    from bodo_tpu.plan import logical as L
    if node.how != "inner":
        return None

    rels: list = []

    def chain(n) -> None:
        if isinstance(n, L.Join) and n.how == "inner" and \
                n.null_equal == node.null_equal and \
                n.suffixes == node.suffixes:
            chain(n.left)
            rels.append(n.right)
        else:
            rels.append(n)

    chain(node)
    if len(rels) < 3:
        return None
    for r in rels:
        exec_cb(r)
    from bodo_tpu.plan import optimizer
    new = optimizer.reorder_joins(node)
    if new is node:
        return None
    try:
        if new.key() == node.key():
            return None
    except Exception:
        return None
    _mark_reoptimized(new)
    count("reoptimize:join_order")
    return new


def _mark_reoptimized(n) -> None:
    from bodo_tpu.plan import logical as L
    if isinstance(n, L.Join):
        n._aqe_reopt = True
    for c in n.children:
        _mark_reoptimized(c)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def stats() -> dict:
    """Decision counters + per-query q-error summary (tracing dump /
    profile aqe:* rows), and how many
    candidate joins the join order priced with a bound on the keys'
    distinct values (`join_est_keyed`) and on row counts alone
    (`join_est_unkeyed`)."""
    with _lock:
        qs = sorted(e["q"] for e in _qerr)
        qe: dict = {"count": len(qs)}
        if qs:
            qe.update({
                "mean": round(sum(qs) / len(qs), 3),
                "p50": round(qs[len(qs) // 2], 3),
                "p90": round(qs[min(int(len(qs) * 0.9), len(qs) - 1)], 3),
                "max": round(qs[-1], 3),
                "worst": [
                    {"stage": e["stage"], "est": round(e["est"], 1),
                     "actual": e["actual"], "q": round(e["q"], 3)}
                    for e in sorted(_qerr, key=lambda e: -e["q"])[:5]],
            })
        return {"enabled": enabled(),
                "decisions": {k: int(v)
                              for k, v in sorted(_counters.items())},
                "q_error": qe,
                "join_est_keyed": _stats_mod._priced["keyed"],
                "join_est_unkeyed": _stats_mod._priced["unkeyed"]}


# install the estimate override once, at import (physical.py imports
# this module, so any execution path activates it; the hook itself
# checks config.aqe per call)
from bodo_tpu.plan import stats as _stats_mod  # noqa: E402

_stats_mod._runtime_override = estimate_override
