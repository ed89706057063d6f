"""Semantic result cache + incremental append maintenance.

Replaces the ad-hoc session dict in plan/physical.py (which keyed on the
raw structural ``node.key()`` — no dataset signature, so an overwritten
parquet file kept serving the stale result, and evicted in insertion
order regardless of how hot an entry was). The cache here keys every
entry on

    (plan fingerprint, environment key, dataset-signature digest)

where the fingerprint is the sha256 of the structural plan key, the
environment key pins the execution geometry (mesh width, shard policy,
precision mode) so mode sweeps never cross-serve, and the signature
digest covers the per-file (path, mtime, size) signatures of every
source the plan reads. A file overwrite changes the digest → natural
invalidation; an identical re-read hits.

Two entry tiers share one store:

  * node entries ("n", …) — per-plan-node memoization across queries,
    the successor of the old session dict;
  * query entries ("q", …) — whole-query results recorded at the
    execute() boundary, carrying everything incremental maintenance
    needs (the rebuildable plan template, per-source signatures, hidden
    aggregation partials).

INCREMENTAL MAINTENANCE: when a parquet dataset's signature changes by
*appended files only* (old signatures byte-identical, new files added —
``io.parquet.classify_change``), and the cached plan is a
concat-safe tree (ReadParquet/Filter/Projection/Union) optionally under
one terminal Aggregate/Reduce whose ops are distributive or algebraic
(sum/count/min/max, mean via hidden sum+count partials), the delta files
are scanned with a rebuilt template plan and spliced into the cached
result through the engine's own kernels:

    concat   : cached ++ delta                     (tail-append only)
    agg      : groupby(concat(cached, delta)) with sum→sum, count→sum,
               min→min, max→max; mean re-finalized from hidden partials
    reduce   : reduce(concat(cached_row, delta_row)), same merge ops

Any non-append change, non-incrementalizable plan, or mid-splice failure
invalidates cleanly to a full run — never a spliced partial.

MEMORY: cached results are device memory the governor must account for.
The cache holds one persistent "result_cache" grant resized to its
device footprint; admission rejects entries larger than the budget;
eviction is by benefit score (saved_wall × hit recency — an entry that
keeps getting hit and saved real wall survives pressure). Query entries
evicted under pressure spill to a host pandas tier (rehydrated — and
re-sharded — on the next hit); ``shed_for_pressure()`` lets the
governor's OOM handler drop the whole device tier rather than OOM a
query to keep a cache entry.

MULTI-TENANCY: every entry is tagged with the serving session that
recorded it (runtime/scheduler.py's contextvar; "-" outside the serving
layer) and per-session device bytes are accounted. Under device
pressure eviction is FAIR-SHARE: with more than one session holding
device entries, victims come from sessions above their equal share of
the budget (lowest benefit score first); when the inserting session is
the only one over its share, its own entry is the victim — a tenant
flooding the cache self-limits to its share and cannot evict another
tenant's within-share working set. ``stats()["by_session"]`` exposes
per-session hit/miss/eviction/byte counters.

OWNERSHIP: the cache is PER-GANG — ownership is the (pid, gang_id)
pair. Device buffers in entries are only valid on the process that
created them, and the byte accounting assumes one governor. ``cache()``
asserts this: a plain fork (different pid, same gang identity) gets a
loud warning and a fresh empty cache instead of silently serving
another process's device handles, while a legitimate fleet gang
process (its own ``BODO_TPU_GANG_ID``) starts its private cache
silently. Cross-gang sharing happens explicitly through the fleet
peering tier (``set_peer_hooks`` / ``peer_export`` /
``invalidate_paths`` — runtime/fleet.py): on a local miss the owning
gang may import a peer's entry via the host pandas exchange format,
and a dataset mutation on any gang broadcasts the mutated source
paths so no peer ever serves a pre-mutation result.

Everything is best-effort: a cache failure must cost a recompute, never
the query.
"""

from __future__ import annotations

import contextlib
import hashlib
import os as _os
import threading
import time
import warnings
from typing import Dict, Optional, Set, Tuple

from bodo_tpu.config import config
from bodo_tpu.utils.logging import log

_HIDDEN_SUM = "__rc_s__"   # hidden mean partials: sum / count per out col
_HIDDEN_CNT = "__rc_c__"
_INCR_AGG_OPS = {"sum", "count", "min", "max", "mean"}
_MERGE_OP = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}
_MAX_ENTRIES = 512         # entry-count backstop on top of the byte budget
_PIN_TIER = 1e9            # score floor per live view dependent (_score)
_AUTO_FRACTION = 0.125     # auto byte budget: slice of the derived budget
_AUTO_FLOOR = 64 << 20
_AUTO_DEFAULT = 256 << 20  # when no governor budget can be derived
HOST_TIER_BYTES = 1 << 28  # byte cap of the host spill tier


# --------------------------------------------------------------------------
# keying: plan fingerprint + source signatures + environment
# --------------------------------------------------------------------------

_epoch = threading.local()


@contextlib.contextmanager
def signature_epoch():
    """One stat() per source per execute: signatures computed inside the
    epoch are snapshotted, so the per-node lookups of a single execute
    all see (and pay for) one consistent view of the filesystem."""
    depth = getattr(_epoch, "depth", 0)
    if depth == 0:
        _epoch.sigs = {}
    _epoch.depth = depth + 1
    try:
        yield
    finally:
        _epoch.depth -= 1
        if _epoch.depth == 0:
            _epoch.sigs = None


def _sources_of(node):
    """Structural source list of a subplan: tuple of ("pq", path) /
    ("csv", path) / ("mem", id), or None when the plan reads something
    the cache cannot sign. Memoized on the node (structure is
    immutable)."""
    s = getattr(node, "_rc_srcs", False)
    if s is not False:
        return s
    from bodo_tpu.plan import logical as L
    if not node.children:
        if isinstance(node, L.ReadParquet):
            s = (("pq", node.path),)
        elif isinstance(node, L.ReadCsv):
            s = (("csv", node.path),)
        elif isinstance(node, L.FromPandas):
            s = (("mem", node._id),)
        elif isinstance(node, L.ViewScan):
            # a view scan signs as its view's BASE sources (resolved
            # transitively through the view DAG): a consumer's key then
            # rolls over exactly when the underlying data changes, even
            # though the consumer reads the cached materialization
            import sys
            vw = sys.modules.get("bodo_tpu.runtime.views")
            s = vw.base_sources(node.name) if vw is not None else None
        else:
            s = None
    else:
        acc = []
        s = ()
        for c in node.children:
            cs = _sources_of(c)
            if cs is None:
                s = None
                break
            acc.extend(cs)
        if s is not None:
            seen: Set = set()
            out = []
            for x in acc:
                if x not in seen:
                    seen.add(x)
                    out.append(x)
            s = tuple(out)
    node._rc_srcs = s
    return s


def _source_sig(kind: str, ident):
    """Content signature for one source, or None (uncacheable). Failures
    are loud-once via the stats store's degraded-signature channel —
    a signature that silently collapses would alias two datasets."""
    cache_d = getattr(_epoch, "sigs", None)
    k = (kind, ident)
    if cache_d is not None and k in cache_d:
        return cache_d[k]
    try:
        if kind == "pq":
            from bodo_tpu.io.parquet import dataset_signature
            sig = dataset_signature(ident)
        elif kind == "csv":
            import os
            st = os.stat(ident)
            sig = ((str(ident), st.st_mtime_ns, st.st_size),)
        else:  # "mem": identity lives in the fingerprint's counter id
            sig = ()
    except Exception as e:  # noqa: BLE001 - uncacheable, not fatal
        from bodo_tpu.runtime import stats_store
        stats_store.note_signature_failure(ident, e)
        sig = None
    if cache_d is not None:
        cache_d[k] = sig
    return sig


def _plan_fp(node) -> str:
    fp = getattr(node, "_rc_fp", None)
    if fp is None:
        fp = hashlib.sha256(repr(node.key()).encode()).hexdigest()[:24]
        node._rc_fp = fp
    return fp


def _env_key() -> tuple:
    """Execution geometry baked into every key: a result computed on one
    mesh/shard policy must not serve a query running under another."""
    from bodo_tpu.parallel import mesh as mesh_mod
    return (mesh_mod.num_shards(), int(config.shard_min_rows))


def _sig_digest(sigs) -> str:
    return hashlib.sha256(repr(sigs).encode()).hexdigest()[:24]


class _QueryInfo:
    __slots__ = ("fp", "env", "sigs", "key", "raw")

    def __init__(self, fp, env, sigs, key, raw):
        self.fp, self.env, self.sigs, self.key, self.raw = \
            fp, env, sigs, key, raw


# --------------------------------------------------------------------------
# incremental-maintenance plan analysis
# --------------------------------------------------------------------------

def _concat_safe(node) -> bool:
    """True when executing the plan over D++Δ equals (plan over D) ++
    (plan over Δ) as a row multiset: per-row operators over scans."""
    from bodo_tpu.plan import logical as L
    if isinstance(node, L.ReadParquet):
        return True
    if isinstance(node, (L.Filter, L.Projection)):
        return _concat_safe(node.child)
    if isinstance(node, L.Union):
        return all(_concat_safe(c) for c in node.children)
    return False


def _parquet_scans(node, out=None):
    from bodo_tpu.plan import logical as L
    if out is None:
        out = []
    if isinstance(node, L.ReadParquet):
        out.append(node)
    for c in node.children:
        _parquet_scans(c, out)
    return out


def _rebuild(node, scan_files=None):
    """Fresh structural clone of an incrementally-maintainable plan (no
    memoized ``_cached`` tables pinned); ``scan_files`` swaps every
    parquet scan's file list — that is the delta plan."""
    from bodo_tpu.plan import logical as L
    if isinstance(node, L.ReadParquet):
        path = node.path if scan_files is None else tuple(scan_files)
        return L.ReadParquet(path, columns=list(node.columns))
    if isinstance(node, L.Filter):
        return L.Filter(_rebuild(node.child, scan_files), node.predicate)
    if isinstance(node, L.Projection):
        return L.Projection(_rebuild(node.child, scan_files), node.exprs)
    if isinstance(node, L.Union):
        return L.Union([_rebuild(c, scan_files) for c in node.children])
    if isinstance(node, L.Aggregate):
        return L.Aggregate(_rebuild(node.child, scan_files), node.keys,
                           node.aggs)
    if isinstance(node, L.Reduce):
        return L.Reduce(_rebuild(node.child, scan_files), node.aggs)
    raise TypeError(f"not incrementally maintainable: "
                    f"{type(node).__name__}")


def _analyze_incremental(root) -> Optional[dict]:
    """Decide whether a plan supports append splicing; when it does,
    return the execution recipe: possibly-augmented exec root (hidden
    sum/count partials for mean re-finalize), the visible column list,
    and a rebuildable template. None → plain full runs only."""
    from bodo_tpu.plan import logical as L
    from bodo_tpu.table import dtypes as dt
    shape = None
    if isinstance(root, (L.Aggregate, L.Reduce)):
        child = root.child
        aggs = root.aggs
        if not _concat_safe(child) or not aggs:
            return None
        for col, op, _out in aggs:
            if op not in _INCR_AGG_OPS:
                return None
            if op == "mean" and not dt.is_numeric(child.schema[col]):
                return None
        shape = "agg" if isinstance(root, L.Aggregate) else "reduce"
    elif _concat_safe(root):
        shape = "concat"
        child = root
    else:
        return None
    scans = _parquet_scans(root)
    if not scans or len({s.path for s in scans}) != 1:
        return None  # exactly one dataset: the delta plan swaps its files
    if shape == "concat" and len(scans) > 1:
        return None  # multi-scan concat would reorder rows on splice
    path = scans[0].path
    import os
    if not os.path.isdir(path):
        # a single-file scan cannot grow by appended files — any change
        # is a mutation, so augmenting (and recompiling) for a future
        # splice would be pure overhead on the hot single-file path
        return None
    keys = list(getattr(root, "keys", []))
    means = []
    exec_root, visible = root, None
    if shape in ("agg", "reduce"):
        exec_aggs = list(aggs)
        taken = set(child.schema) | set(keys) | {o for _c, _o2, o in aggs}
        for col, op, out in aggs:
            if op != "mean":
                continue
            s_name, c_name = _HIDDEN_SUM + out, _HIDDEN_CNT + out
            if s_name in taken or c_name in taken:
                return None  # hidden-name collision: bail out entirely
            taken |= {s_name, c_name}
            exec_aggs.append((col, "sum", s_name))
            exec_aggs.append((col, "count", c_name))
            means.append((out, s_name, c_name))
        if means:
            exec_root = (L.Aggregate(child, keys, exec_aggs)
                         if shape == "agg" else L.Reduce(child, exec_aggs))
            visible = list(root.schema)
        aggs = exec_aggs
    else:
        aggs = []
    return {"shape": shape, "keys": keys, "aggs": aggs, "means": means,
            "order": list(exec_root.schema), "path": path,
            "exec_root": exec_root, "visible": visible,
            "template": _rebuild(exec_root)}


def _refinalize_means(merged, incr, proto):
    """mean = hidden_sum / hidden_count, mirroring the groupby kernel's
    finalize (s / max(cnt, 1), NaN where the group is empty) in the
    result dtype the original plan produced."""
    import jax.numpy as jnp

    from bodo_tpu.table.table import Column
    cols = dict(merged.columns)
    for out, s_name, c_name in incr["means"]:
        rdt = proto.columns[out].dtype
        sv = cols[s_name].data.astype(rdt.numpy)
        cv = cols[c_name].data
        m = sv / jnp.maximum(cv, 1)
        m = jnp.where(cv > 0, m, jnp.nan).astype(rdt.numpy)
        cols[out] = Column(m, None, rdt)
    return merged.with_columns(cols)


def _splice(old_t, delta_t, incr):
    """Merge a delta-plan result into the cached result through the
    engine's own kernels — same code paths, same dtypes, same
    distribution policy as a full run."""
    from bodo_tpu import relational as R
    if list(delta_t.names) != list(old_t.names):
        delta_t = delta_t.select(old_t.names)
    shape = incr["shape"]
    if shape == "concat":
        from bodo_tpu.plan import physical
        return physical._maybe_shard(R.concat_tables([old_t, delta_t]))
    merge = [(out, _MERGE_OP[op], out)
             for _c, op, out in incr["aggs"] if op != "mean"]
    both = R.concat_tables([old_t, delta_t])
    if shape == "agg":
        merged = R.groupby_agg(both, incr["keys"], merge)
        if incr["means"]:
            merged = _refinalize_means(merged, incr, old_t)
        return merged.select(incr["order"])
    # reduce: merge the two 1-row partial tables, re-finalize means the
    # same way reduce_table's host finalize does (sum / count, NaN empty)
    import pandas as pd

    from bodo_tpu.table.table import Table
    scalars = R.reduce_table(both, merge)
    for out, s_name, c_name in incr["means"]:
        cnt = int(scalars[c_name])
        scalars[out] = float(scalars[s_name]) / cnt if cnt \
            else float("nan")
    df = pd.DataFrame({k: [scalars[k]] for k in incr["order"]})
    return Table.from_pandas(df)


def _classify_append(old_sigs, new_sigs):
    """(delta_files, tail_only) when every source change is append-only;
    None on any mutate/mixed change. ``tail_only`` is True when the
    delta files strictly follow the old files in scan order — required
    for concat-shape splices, which must preserve row order."""
    if len(old_sigs) != len(new_sigs):
        return None
    from bodo_tpu.io.parquet import classify_change
    delta = []
    tail_only = True
    changed = False
    for (ok_, oid, osig), (nk, nid, nsig) in zip(old_sigs, new_sigs):
        if ok_ != nk or oid != nid:
            return None
        if osig == nsig:
            continue
        if ok_ != "pq":
            return None
        verdict, files = classify_change(osig, nsig)
        if verdict != "append":
            return None
        changed = True
        delta.extend(files)
        if tuple(nsig[:len(osig)]) != tuple(osig):
            # an in-place grown file keeps its old rows where they were;
            # the growth is tail-ordered only when the grown file is the
            # LAST old file in scan order (its new row groups then follow
            # every cached row, so a concat splice stays row-ordered)
            grown = {str(f).rpartition("#rg=")[0] for f in files
                     if "#rg=" in str(f)}
            prefix_ok = all(a == b for a, b in zip(osig[:-1], nsig)) \
                if osig else False
            last_o = osig[-1] if osig else None
            last_n = nsig[len(osig) - 1] if osig else None
            if not (prefix_ok and last_o[0] == last_n[0]
                    and last_o[0] in grown):
                tail_only = False
    if not changed or not delta:
        return None
    return tuple(delta), tail_only


# --------------------------------------------------------------------------
# the cache
# --------------------------------------------------------------------------

def _gang_id() -> str:
    """This process's fleet gang identity ("" outside fleet mode). Read
    from the environment, not config — ownership checks must agree with
    what the fleet controller exported at spawn time."""
    return _os.environ.get("BODO_TPU_GANG_ID", "")


def _current_session() -> str:
    """Serving-session label for attribution ("-" outside the serving
    layer). Read via sys.modules.get — recording a cache entry must
    never import the scheduler."""
    import sys
    sch = sys.modules.get("bodo_tpu.runtime.scheduler")
    if sch is None:
        return "-"
    try:
        return sch.current_session() or "-"
    except Exception:  # noqa: BLE001 - attribution is best-effort
        return "-"


class _Entry:
    __slots__ = ("key", "raw", "kind", "table", "host", "dist", "nbytes",
                 "host_nbytes", "saved_wall_s", "hits", "last_use",
                 "sources", "visible", "incr", "session", "parts",
                 "parts_nbytes")

    def __init__(self, key, raw, kind):
        self.key, self.raw, self.kind = key, raw, kind
        self.table = None
        self.host = None
        self.dist = None
        self.nbytes = 0
        self.host_nbytes = 0
        self.saved_wall_s = 0.0
        self.hits = 0
        self.last_use = 0.0
        self.sources = None
        self.visible = None
        self.incr = None
        self.session = "-"
        # partition-level invalidation: per-source-file host partials of
        # the exec-root output ({file path -> pandas}), so a mutate of
        # ONE file re-runs one delta plan and re-merges instead of
        # nuking the whole entry (see _try_partition_refresh)
        self.parts = None
        self.parts_nbytes = 0


class ResultCache:
    """Two-tier (device Table / host pandas) semantic result store with
    benefit-scored eviction and governor-charged admission."""

    def __init__(self):
        self._mu = threading.RLock()
        self._entries: Dict[tuple, _Entry] = {}
        self._by_fp: Dict[tuple, tuple] = {}    # (fp, env) -> query key
        self._by_raw: Dict[tuple, Set[tuple]] = {}
        self._refs: Dict[int, list] = {}        # id(table) -> [refs, bytes]
        self.device_bytes = 0
        self.host_bytes = 0
        self.saved_wall_s = 0.0
        self._grant = None
        self._grant_bytes = 0
        self._budget_cache: Optional[int] = None
        self._budget_at = 0.0
        self._c: Dict[str, int] = {}
        self._sess: Dict[str, Dict[str, int]] = {}  # session -> counters
        # plan fingerprint -> live dependent count (downstream views +
        # subscribers); weights eviction benefit so a view DAG root is
        # not evicted under its own fan-out (runtime/views.py maintains)
        self._view_pins: Dict[str, int] = {}
        self._owner_pid = _os.getpid()
        self._owner_gang = _gang_id()

    # -- plumbing ------------------------------------------------------------

    def _now(self) -> float:
        return time.monotonic()

    def count(self, name: str, n: int = 1) -> None:
        with self._mu:
            self._c[name] = self._c.get(name, 0) + n

    def _count_sess_locked(self, session: str, name: str,
                           n: int = 1) -> None:
        d = self._sess.setdefault(session or "-", {})
        d[name] = d.get(name, 0) + n

    def assert_single_gang_owner(self) -> None:
        """Hard ownership check: this cache's device buffers belong to
        the (pid, gang_id) that created them."""
        if (self._owner_pid, self._owner_gang) != \
                (_os.getpid(), _gang_id()):
            raise AssertionError(
                f"result cache owned by pid={self._owner_pid} "
                f"gang={self._owner_gang or '-'} used from "
                f"pid={_os.getpid()} gang={_gang_id() or '-'}: device "
                f"entries are per-gang; fleet gangs each own a private "
                f"cache (BODO_TPU_GANG_ID) and exchange results via "
                f"the peering tier (runtime/fleet.py)")

    def _device_budget(self) -> int:
        b = int(config.result_cache_bytes)
        if b > 0:
            return b
        # auto mode re-probes the governor's derived budget at most
        # once a second: this sits on the per-node record path
        now = self._now()
        if self._budget_cache is not None \
                and now - self._budget_at < 1.0:
            return self._budget_cache
        try:
            from bodo_tpu.runtime.memory_governor import governor
            derived = governor().derived_budget()
        except Exception:  # noqa: BLE001
            derived = 0
        out = max(_AUTO_FLOOR, int(derived * _AUTO_FRACTION)) \
            if derived else _AUTO_DEFAULT
        self._budget_cache, self._budget_at = out, now
        return out

    def _score(self, e: _Entry) -> float:
        """Benefit = saved wall × hit recency: evicting min keeps the
        entries that keep earning their memory. A view materialization
        serving N live dependents (downstream views + subscribers) is
        guaranteed future reuse on a schedule LRU cannot see (the next
        maintenance pass, not the next user query), so pinned entries
        rank a whole tier above every unpinned candidate — saved wall
        can be milliseconds on a warm gang and no multiplier of it
        reliably beats a freshly-recorded scan. Within the pinned
        tier, more dependents and saved wall still order victims; the
        eviction loop can still reclaim pinned entries once they are
        the only candidates left, so the budget always wins."""
        if e.kind == "q" and self._view_pins:
            deps = self._view_pins.get(e.key[1], 0)
            if deps:
                return _PIN_TIER * deps + e.saved_wall_s * (1.0 + e.hits)
        age = max(self._now() - e.last_use, 0.0)
        return (e.saved_wall_s * (1.0 + e.hits)) / (age + 1.0)

    def set_view_pin(self, fp: str, deps: int) -> None:
        """Declare fp's live dependent count (0 clears the pin)."""
        with self._mu:
            if deps > 0:
                self._view_pins[fp] = int(deps)
            else:
                self._view_pins.pop(fp, None)

    def clear_view_pins(self) -> None:
        with self._mu:
            self._view_pins.clear()

    def _sync_grant_locked(self) -> None:
        """Keep one persistent governor grant sized to the device
        footprint, so cached results are visible memory pressure.
        Resyncs are throttled to >=1 MiB drift: the grant is advisory
        accounting and this sits on the per-node record path."""
        if not config.mem_governor:
            return
        if self._grant is not None and self.device_bytes > 0 and \
                abs(self.device_bytes - self._grant_bytes) < (1 << 20):
            return
        try:
            from bodo_tpu.runtime import memory_governor as mg
            if self.device_bytes <= 0:
                if self._grant is not None:
                    g, self._grant = self._grant, None
                    self._grant_bytes = 0
                    g.release()
                return
            gov = mg.governor()
            if self._grant is None:
                self._grant = gov.admit("result_cache",
                                        want=self.device_bytes,
                                        wait=False)
            gov.resize_grant(self._grant, self.device_bytes)
            self._grant_bytes = self.device_bytes
        except Exception:  # noqa: BLE001 - accounting is best-effort
            pass

    def _charge_locked(self, e: _Entry, table, nbytes: int) -> None:
        r = self._refs.get(id(table))
        if r is None:
            self._refs[id(table)] = [1, nbytes]
            self.device_bytes += nbytes
        else:
            r[0] += 1
        e.table = table
        e.nbytes = nbytes

    def _deref_locked(self, e: _Entry) -> None:
        t = e.table
        if t is None:
            return
        e.table = None
        r = self._refs.get(id(t))
        if r is not None:
            r[0] -= 1
            if r[0] <= 0:
                self.device_bytes -= r[1]
                del self._refs[id(t)]

    def _drop_locked(self, e: _Entry) -> None:
        self._deref_locked(e)
        if e.host is not None:
            self.host_bytes -= e.host_nbytes
            e.host, e.host_nbytes = None, 0
        if e.parts is not None:
            self.host_bytes -= e.parts_nbytes
            e.parts, e.parts_nbytes = None, 0
        self._entries.pop(e.key, None)
        ks = self._by_raw.get(e.raw)
        if ks is not None:
            ks.discard(e.key)
            if not ks:
                del self._by_raw[e.raw]
        if e.kind == "q":
            fpk = (e.key[1], e.key[2])
            if self._by_fp.get(fpk) == e.key:
                del self._by_fp[fpk]

    def _spill_locked(self, e: _Entry) -> None:
        """Device → host pandas tier (query entries only — node-level
        memoization is not worth a host copy)."""
        if e.kind != "q" or not config.result_cache_host_spill:
            self._drop_locked(e)
            return
        try:
            df = e.table.to_pandas()
            nb = int(df.memory_usage(deep=True).sum())
        except Exception:  # noqa: BLE001
            self._drop_locked(e)
            return
        self._deref_locked(e)
        e.host = df
        e.host_nbytes = nb
        self.host_bytes += nb
        self._c["spills"] = self._c.get("spills", 0) + 1

    def _rehydrate_locked(self, e: _Entry):
        """Host → device on a hit, restoring the original distribution
        (a 1D result re-shards over the current mesh)."""
        from bodo_tpu.parallel import mesh as mesh_mod
        from bodo_tpu.runtime.memory_governor import table_device_bytes
        from bodo_tpu.table.table import ONED, Table
        t = Table.from_pandas(e.host)
        if e.dist == ONED and mesh_mod.num_shards() > 1:
            t = t.shard()
        nb = int(table_device_bytes(t))
        self.host_bytes -= e.host_nbytes
        e.host, e.host_nbytes = None, 0
        self._charge_locked(e, t, nb)
        self._c["rehydrations"] = self._c.get("rehydrations", 0) + 1
        self._evict_locked(keep=e.key)
        self._sync_grant_locked()
        return t

    def _sess_dev_locked(self) -> Dict[str, int]:
        """Per-session device bytes (entry-attributed: a table shared
        across sessions counts toward each holder's footprint, which is
        the conservative side for fair-share comparisons)."""
        by: Dict[str, int] = {}
        for e in self._entries.values():
            if e.table is not None:
                by[e.session] = by.get(e.session, 0) + e.nbytes
        return by

    def _device_victim_locked(self, budget: int, keep) -> Optional[_Entry]:
        """Fair-share victim choice. Single tenant: global min benefit
        score (original behavior). Multiple tenants: victims come from
        sessions above their equal share of the budget; when only the
        inserting (keep) session is over its share, ITS entry is the
        victim — a flooding tenant self-limits instead of evicting a
        within-share working set of another tenant."""
        cands = [e for e in self._entries.values()
                 if e.table is not None and e.key != keep]
        by_sess = self._sess_dev_locked()
        if len(by_sess) > 1:
            share = budget // len(by_sess)
            over = [e for e in cands if by_sess.get(e.session, 0) > share]
            if over:
                return min(over, key=self._score)
            keep_e = self._entries.get(keep) if keep is not None else None
            if keep_e is not None and keep_e.table is not None \
                    and by_sess.get(keep_e.session, 0) > share:
                return keep_e
        if not cands:
            cands = [e for e in self._entries.values()
                     if e.table is not None]
        return min(cands, key=self._score) if cands else None

    def _evict_locked(self, keep=None) -> None:
        budget = self._device_budget()
        while self.device_bytes > budget:
            victim = self._device_victim_locked(budget, keep)
            if victim is None:
                break
            self._c["evictions"] = self._c.get("evictions", 0) + 1
            self._count_sess_locked(victim.session, "evicted")
            self._spill_locked(victim)
        while self.host_bytes > HOST_TIER_BYTES:
            cands = [e for e in self._entries.values()
                     if e.host is not None]
            if not cands:
                break
            self._drop_locked(min(cands, key=self._score))
        while len(self._entries) > _MAX_ENTRIES:
            cands = [e for e in self._entries.values() if e.key != keep]
            if not cands:
                break
            victim = min(cands, key=self._score)
            self._c["evictions"] = self._c.get("evictions", 0) + 1
            self._count_sess_locked(victim.session, "evicted")
            self._drop_locked(victim)

    # -- store/lookup --------------------------------------------------------

    def record(self, key, raw, table, wall_s, *, kind="n", sources=None,
               visible=None, incr=None) -> None:
        if key is None or not config.result_cache:
            return
        try:
            from bodo_tpu.runtime.memory_governor import \
                table_device_bytes
            nbytes = int(table_device_bytes(table))
        except Exception:  # noqa: BLE001
            nbytes = 0
        session = _current_session()
        with self._mu:
            if nbytes > self._device_budget():
                self._c["rejected"] = self._c.get("rejected", 0) + 1
                self._count_sess_locked(session, "rejected")
                return
            old = self._entries.get(key)
            if old is not None:
                self._drop_locked(old)
            e = _Entry(key, raw, kind)
            e.saved_wall_s = max(float(wall_s), 0.0)
            e.last_use = self._now()
            e.dist = table.distribution
            e.sources = sources
            e.visible = visible
            e.incr = incr
            e.session = session
            self._count_sess_locked(session, "records")
            self._entries[key] = e
            self._charge_locked(e, table, nbytes)
            self._by_raw.setdefault(raw, set()).add(key)
            if kind == "q":
                self._by_fp[(key[1], key[2])] = key
            self._evict_locked(keep=key)
            self._sync_grant_locked()

    def lookup(self, key, *, prefix: str = ""):
        """Table for a key, counting {prefix}hits/{prefix}misses; host
        entries rehydrate transparently."""
        if key is None or not config.result_cache:
            return None
        session = _current_session()
        with self._mu:
            e = self._entries.get(key)
            if e is None:
                self._c[prefix + "misses"] = \
                    self._c.get(prefix + "misses", 0) + 1
                self._count_sess_locked(session, prefix + "misses")
                return None
            e.hits += 1
            e.last_use = self._now()
            t = e.table
            if t is None:
                try:
                    t = self._rehydrate_locked(e)
                except Exception:  # noqa: BLE001
                    self._drop_locked(e)
                    self._c[prefix + "misses"] = \
                        self._c.get(prefix + "misses", 0) + 1
                    self._count_sess_locked(session, prefix + "misses")
                    return None
            self._c[prefix + "hits"] = self._c.get(prefix + "hits", 0) + 1
            self._count_sess_locked(session, prefix + "hits")
            self.saved_wall_s += e.saved_wall_s
            return t

    def attach_parts(self, key, parts) -> bool:
        """Attach (or replace) an entry's per-source-file contribution
        map; partials are host pandas, charged to the host tier."""
        try:
            nb = sum(int(df.memory_usage(deep=True).sum())
                     for df in parts.values())
        except Exception:  # noqa: BLE001
            return False
        with self._mu:
            e = self._entries.get(key)
            if e is None:
                return False
            if e.parts is not None:
                self.host_bytes -= e.parts_nbytes
            e.parts = dict(parts)
            e.parts_nbytes = nb
            self.host_bytes += nb
            return True

    def build_parts(self, key, run, max_parts: Optional[int] = None) \
            -> bool:
        """Build the contribution map for an incrementalizable cached
        entry: one delta plan per source file, partials in NEW-scan-order
        merge form. Skipped (False) past ``max_parts`` files — the map
        costs one pass over the dataset, paid once per materialization."""
        with self._mu:
            e = self._entries.get(key)
            if e is None or e.incr is None or not e.sources:
                return False
            if len(e.sources) != 1 or e.sources[0][0] != "pq":
                return False
            files = [s[0] for s in e.sources[0][2]]
            incr = e.incr
        if not files or (max_parts is not None
                         and len(files) > max_parts):
            return False
        parts = {}
        try:
            for f in files:
                droot = _rebuild(incr["template"], scan_files=(f,))
                parts[f] = run(droot).to_pandas()
        except Exception:  # noqa: BLE001 - the map is an optimization
            return False
        self.count("parts_built", len(files))
        return self.attach_parts(key, parts)

    def _merge_parts(self, parts, order, incr):
        """Merge per-file partials (NEW scan order) through the same
        kernels a splice uses — same dtypes, same distribution policy."""
        import pandas as pd

        from bodo_tpu import relational as R
        from bodo_tpu.table.table import Table
        df = pd.concat([parts[f] for f in order], ignore_index=True)
        t = Table.from_pandas(df)
        shape = incr["shape"]
        if shape == "concat":
            from bodo_tpu.plan import physical
            return physical._maybe_shard(t)
        merge = [(out, _MERGE_OP[op], out)
                 for _c, op, out in incr["aggs"] if op != "mean"]
        if shape == "agg":
            merged = R.groupby_agg(t, incr["keys"], merge)
            if incr["means"]:
                merged = _refinalize_means(merged, incr, t)
            return merged.select(incr["order"])
        scalars = R.reduce_table(t, merge)
        for out, s_name, c_name in incr["means"]:
            cnt = int(scalars[c_name])
            scalars[out] = float(scalars[s_name]) / cnt if cnt \
                else float("nan")
        df2 = pd.DataFrame({k: [scalars[k]] for k in incr["order"]})
        return Table.from_pandas(df2)

    def _try_partition_refresh(self, root, prev, qi, run):
        """Partition-level invalidation: when the superseded entry
        carries a contribution map and the change mutated/added SOME
        files in place (no deletions), re-run delta plans for only those
        files and re-merge — unaffected partitions re-serve their cached
        partials without recompute. Any ambiguity (deleted file, partial
        missing from the map, merge failure) returns None and the caller
        falls back to full invalidation — never a stale partial."""
        if prev.incr is None or not prev.sources or prev.parts is None:
            return None
        if len(prev.sources) != 1 or len(qi.sigs) != 1:
            return None
        (ok_, oid, osig), (nk, nid, nsig) = prev.sources[0], qi.sigs[0]
        if ok_ != "pq" or nk != "pq" or oid != nid:
            return None
        old_by = {s[0]: s for s in osig}
        new_by = {s[0]: s for s in nsig}
        if any(p not in new_by for p in old_by):
            return None  # deletion: no partial split can be trusted
        changed = [s[0] for s in nsig
                   if s[0] in old_by and old_by[s[0]] != s]
        added = [s[0] for s in nsig if s[0] not in old_by]
        if not changed and not added:
            return None
        if any(p not in prev.parts for p in changed):
            return None
        t0 = time.perf_counter()
        try:
            parts = dict(prev.parts)
            for f in changed + added:
                droot = _rebuild(prev.incr["template"], scan_files=(f,))
                droot._explain_path = getattr(root, "_explain_path",
                                              None)
                parts[f] = run(droot).to_pandas()
            order = [s[0] for s in nsig]
            merged = self._merge_parts(parts, order, prev.incr)
        except Exception as e:  # noqa: BLE001 - never a stale partial
            self.count("incremental_fallbacks")
            log(1, f"result cache: partition refresh failed "
                   f"({type(e).__name__}: {e}); falling back to full "
                   f"invalidation")
            return None
        wall = time.perf_counter() - t0
        self.count("partition_refresh")
        self.count("parts_reused",
                   len(order) - len(changed) - len(added))
        self.record(qi.key, qi.raw, merged, prev.saved_wall_s, kind="q",
                    sources=qi.sigs, visible=prev.visible,
                    incr=prev.incr)
        self.attach_parts(qi.key, parts)
        with self._mu:
            if self._entries.get(prev.key) is prev:
                self._drop_locked(prev)
            self._sync_grant_locked()
        log(1, f"result cache: partition refresh over "
               f"{len(changed) + len(added)} of {len(order)} file(s) "
               f"in {wall:.3f}s")
        _explain_rcache(root, merged,
                        {"event": "partition_refresh",
                         "changed_files": len(changed) + len(added),
                         "wall_s": round(wall, 6)})
        vis = prev.visible
        return merged.select(vis) if vis else merged

    def _materialize(self, e: _Entry):
        """Device table for an entry the caller already holds (no hit
        accounting) — None when it vanished or cannot rehydrate."""
        with self._mu:
            if self._entries.get(e.key) is not e:
                return None
            e.last_use = self._now()
            if e.table is not None:
                return e.table
            try:
                return self._rehydrate_locked(e)
            except Exception:  # noqa: BLE001
                self._drop_locked(e)
                return None

    # -- query boundary ------------------------------------------------------

    def _query_info(self, root) -> Optional[_QueryInfo]:
        if not config.result_cache:
            return None
        srcs = _sources_of(root)
        if srcs is None:
            return None
        sigs = []
        for kind, ident in srcs:
            s = _source_sig(kind, ident)
            if s is None:
                self.count("sig_uncacheable")
                return None
            sigs.append((kind, ident, s))
        sigs = tuple(sigs)
        fp = _plan_fp(root)
        env = _env_key()
        key = ("q", fp, env, _sig_digest(sigs))
        return _QueryInfo(fp, env, sigs, key, root.key())

    def cached_execute(self, root, run):
        """The execute() boundary: exact hit → serve; append-only change
        on an incrementalizable cached plan → delta scan + splice; any
        other change → invalidate + full run; miss → timed full run,
        recorded (with hidden partials when the plan supports future
        splices)."""
        if not config.result_cache:
            return run(root)
        with signature_epoch():
            try:
                qi = self._query_info(root)
            except Exception:  # noqa: BLE001 - keying must never fail exec
                qi = None
            if qi is None:
                return run(root)
            with self._mu:
                e = self._entries.get(qi.key)
                saved = e.saved_wall_s if e is not None else 0.0
            t = self.lookup(qi.key, prefix="q_")
            if t is not None:
                vis = e.visible if e is not None else None
                _explain_rcache(root, t, {"event": "hit",
                                          "saved_s": round(saved, 6)})
                return t.select(vis) if vis else t
            with self._mu:
                pk = self._by_fp.get((qi.fp, qi.env))
                prev = self._entries.get(pk) if pk is not None else None
            if prev is not None and prev.key != qi.key:
                out = self._try_incremental(root, prev, qi, run)
                if out is None:
                    out = self._try_partition_refresh(root, prev, qi,
                                                      run)
                if out is not None:
                    return out
                # same plan over changed data and no clean splice: the
                # stale entry can never be served again — drop it, and
                # tell the fleet (when peered) so no other gang serves
                # its copy of the pre-mutation result
                with self._mu:
                    if self._entries.get(prev.key) is prev:
                        self._drop_locked(prev)
                        self._c["invalidations"] = \
                            self._c.get("invalidations", 0) + 1
                    self._sync_grant_locked()
                self._notify_invalidated(prev)
            t = self._peer_fill(root, qi)
            if t is not None:
                return t
            return self._full_run(root, qi, run)

    def _full_run(self, root, qi, run):
        try:
            incr = _analyze_incremental(root)
        except Exception:  # noqa: BLE001 - analysis must never fail exec
            incr = None
        exec_root = incr["exec_root"] if incr else root
        visible = incr["visible"] if incr else None
        if exec_root is not root:
            # augmented plan: inherit the root's EXPLAIN identity and
            # give it its own fusion annotations (best-effort)
            exec_root._explain_path = getattr(root, "_explain_path", None)
            try:
                from bodo_tpu.plan.fusion import plan_fusion_groups
                plan_fusion_groups(exec_root)
            except Exception:  # noqa: BLE001
                pass
        t0 = time.perf_counter()
        t = run(exec_root)
        wall = time.perf_counter() - t0
        entry_incr = None
        if incr:
            entry_incr = {k: incr[k] for k in
                          ("shape", "keys", "aggs", "means", "order",
                           "path", "template")}
        self.record(qi.key, qi.raw, t, wall, kind="q", sources=qi.sigs,
                    visible=visible, incr=entry_incr)
        return t.select(visible) if visible else t

    def _try_incremental(self, root, prev, qi, run):
        """Delta scan + splice against a superseded entry; None when the
        change is not append-only, the plan does not support it, or the
        splice fails (caller falls back to a clean full run)."""
        if prev.incr is None or prev.sources is None:
            return None
        try:
            appended = _classify_append(prev.sources, qi.sigs)
        except Exception:  # noqa: BLE001
            appended = None
        if appended is None:
            return None
        delta_files, tail_only = appended
        if prev.incr["shape"] == "concat" and not tail_only:
            return None
        t0 = time.perf_counter()
        try:
            old_t = self._materialize(prev)
            if old_t is None:
                return None
            delta_root = _rebuild(prev.incr["template"],
                                  scan_files=delta_files)
            delta_root._explain_path = getattr(root, "_explain_path",
                                               None)
            delta_t = run(delta_root)
            merged = _splice(old_t, delta_t, prev.incr)
        except Exception as e:  # noqa: BLE001 - never a spliced partial
            self.count("incremental_fallbacks")
            log(1, f"result cache: incremental refresh failed "
                   f"({type(e).__name__}: {e}); falling back to full "
                   f"run")
            return None
        wall = time.perf_counter() - t0
        self.count("q_incremental")
        # the refreshed entry inherits the superseded entry's benefit
        # estimate: serving it still saves a full recompute
        self.record(qi.key, qi.raw, merged, prev.saved_wall_s, kind="q",
                    sources=qi.sigs, visible=prev.visible,
                    incr=prev.incr)
        with self._mu:
            if self._entries.get(prev.key) is prev:
                self._drop_locked(prev)
            self._sync_grant_locked()
        log(1, f"result cache: incremental refresh over "
               f"{len(delta_files)} appended file(s) in {wall:.3f}s")
        _explain_rcache(root, merged,
                        {"event": "incremental",
                         "delta_files": len(delta_files),
                         "wall_s": round(wall, 6)})
        vis = prev.visible
        return merged.select(vis) if vis else merged

    # -- fleet peering -------------------------------------------------------

    def _peer_fill(self, root, qi):
        """On a local q-miss, ask the fleet peering tier (when hooked)
        for the fingerprint's previous owner's copy before recomputing.
        A successful import is recorded locally like a fresh result, so
        the NEXT repeat is a plain device hit."""
        fetch = _peer_fetch
        if fetch is None:
            return None
        try:
            payload = fetch(qi.key)
        except Exception:  # noqa: BLE001 - peering is best-effort
            payload = None
        if not payload:
            self.count("peer_misses")
            return None
        try:
            from bodo_tpu.parallel import mesh as mesh_mod
            from bodo_tpu.table.table import Table
            t = Table.from_pandas(payload["df"])
            if payload.get("dist") == "1D" and mesh_mod.num_shards() > 1:
                t = t.shard()
        except Exception:  # noqa: BLE001 - a bad payload costs a rerun
            self.count("peer_misses")
            return None
        self.count("peer_hits")
        vis = payload.get("visible")
        self.record(qi.key, qi.raw, t,
                    float(payload.get("saved_wall_s", 0.0)), kind="q",
                    sources=qi.sigs, visible=vis)
        _explain_rcache(root, t, {"event": "peer_hit"})
        return t.select(vis) if vis else t

    def peer_export(self, key):
        """Serve a cached query entry to a peer gang in the host
        exchange format (pandas + distribution/visibility metadata);
        None on miss. The importer re-shards for its own mesh."""
        if not config.result_cache:
            return None
        with self._mu:
            e = self._entries.get(key)
            if e is None or e.kind != "q":
                return None
            try:
                from bodo_tpu.table.table import ONED
                df = e.host if e.host is not None \
                    else e.table.to_pandas()
                payload = {
                    "df": df,
                    "dist": "1D" if e.dist == ONED else "REP",
                    "visible": e.visible,
                    "saved_wall_s": e.saved_wall_s,
                }
            except Exception:  # noqa: BLE001 - export must never raise
                return None
            self._c["peer_serves"] = self._c.get("peer_serves", 0) + 1
            return payload

    def invalidate_paths(self, paths) -> int:
        """Fleet invalidation broadcast receiver: drop every entry whose
        source identities intersect ``paths`` (plus a conservative
        repr-substring match for entries without structured sources).
        Returns entries dropped; never re-broadcasts."""
        if not paths:
            return 0
        pset = {str(p) for p in paths}
        dropped = 0
        with self._mu:
            for e in list(self._entries.values()):
                if e.sources:
                    idents = {str(s[1]) for s in e.sources}
                    hit = bool(idents & pset)
                else:
                    r = repr(e.raw)
                    hit = any(p in r for p in pset)
                if hit:
                    self._drop_locked(e)
                    dropped += 1
            if dropped:
                self._c["invalidations_remote"] = \
                    self._c.get("invalidations_remote", 0) + dropped
            self._sync_grant_locked()
        # fleet-wide VIEW invalidation rides the same broadcast: any
        # registered view whose base sources intersect the mutated
        # paths goes stale on this gang too (best-effort, lazy-module)
        import sys
        vw = sys.modules.get("bodo_tpu.runtime.views")
        if vw is not None:
            try:
                vw.note_invalidated_paths(pset)
            except Exception:  # noqa: BLE001
                pass
        return dropped

    def _notify_invalidated(self, prev) -> None:
        """Tell the fleet (when hooked) which source datasets just
        invalidated a cached result, so the controller can broadcast
        and no peer serves its pre-mutation copy."""
        notify = _peer_notify
        if notify is None:
            return
        try:
            paths = tuple(str(s[1]) for s in (prev.sources or ()))
            if paths:
                notify(paths)
        except Exception:  # noqa: BLE001 - peering is best-effort
            pass

    # -- pressure / lifecycle ------------------------------------------------

    def shed_for_pressure(self) -> int:
        """Governor OOM response: push the whole device tier to host (or
        drop it) — a cache entry must never OOM a live query. Returns
        device bytes freed."""
        if not config.result_cache:
            return 0
        with self._mu:
            before = self.device_bytes
            for e in list(self._entries.values()):
                if e.table is not None:
                    self._spill_locked(e)
            self._evict_locked()
            self._sync_grant_locked()
            freed = before - self.device_bytes
            if freed > 0:
                self._c["pressure_sheds"] = \
                    self._c.get("pressure_sheds", 0) + 1
            return freed

    def reconfigure(self) -> None:
        """config.set_config hook: re-apply knobs (drop everything when
        disabled, re-enforce budgets when resized)."""
        if not config.result_cache:
            self.clear()
            return
        with self._mu:
            self._budget_cache = None
            self._evict_locked()
            self._sync_grant_locked()

    def clear(self) -> None:
        with self._mu:
            for e in list(self._entries.values()):
                self._drop_locked(e)
            self._entries.clear()
            self._by_fp.clear()
            self._by_raw.clear()
            self._refs.clear()
            self.device_bytes = 0
            self.host_bytes = 0
            self._sync_grant_locked()

    def pop(self, raw, default=None):
        """Dict-compat invalidation by RAW plan key — the fusion layer
        pops a node's entries after donating its buffers to XLA."""
        with self._mu:
            for k in list(self._by_raw.get(raw, ())):
                e = self._entries.get(k)
                if e is not None:
                    self._drop_locked(e)
            self._sync_grant_locked()
        return default

    def __len__(self) -> int:
        with self._mu:
            return len(self._entries)

    def __contains__(self, raw) -> bool:
        with self._mu:
            return raw in self._by_raw

    def reset_stats(self) -> None:
        with self._mu:
            self._c.clear()
            self._sess.clear()
            self.saved_wall_s = 0.0

    def stats(self) -> dict:
        with self._mu:
            d = {k: int(v) for k, v in self._c.items()}
            for k in ("hits", "misses", "q_hits", "q_misses",
                      "q_incremental", "evictions", "invalidations",
                      "incremental_fallbacks", "spills", "rehydrations",
                      "rejected", "sig_uncacheable", "pressure_sheds",
                      "peer_hits", "peer_misses", "peer_serves",
                      "invalidations_remote", "partition_refresh",
                      "parts_built", "parts_reused"):
                d.setdefault(k, 0)
            dev = sum(1 for e in self._entries.values()
                      if e.table is not None)
            host = sum(1 for e in self._entries.values()
                       if e.host is not None)
            qh, qm = d["q_hits"], d["q_misses"]
            d.update(entries=len(self._entries), device_entries=dev,
                     host_entries=host, device_bytes=self.device_bytes,
                     host_bytes=self.host_bytes,
                     budget_bytes=self._device_budget(),
                     saved_wall_s=round(self.saved_wall_s, 6),
                     q_hit_rate=(qh / (qh + qm)) if (qh + qm) else 0.0,
                     enabled=bool(config.result_cache),
                     view_pins=len(self._view_pins),
                     owner_pid=self._owner_pid,
                     owner_gang=self._owner_gang)
            by_dev = self._sess_dev_locked()
            by_ent: Dict[str, int] = {}
            for e in self._entries.values():
                by_ent[e.session] = by_ent.get(e.session, 0) + 1
            by = {}
            for sid in set(self._sess) | set(by_ent):
                row = dict(self._sess.get(sid, {}))
                for k in ("q_hits", "q_misses", "hits", "misses",
                          "evicted", "records", "rejected"):
                    row.setdefault(k, 0)
                row["entries"] = by_ent.get(sid, 0)
                row["device_bytes"] = by_dev.get(sid, 0)
                by[sid] = row
            d["by_session"] = by
            return d


def _explain_rcache(root, t, info: dict) -> None:
    """EXPLAIN ANALYZE annotation for a cache-served / spliced root."""
    try:
        from bodo_tpu.utils import tracing
        if not tracing.is_tracing():
            return
        from bodo_tpu.plan import explain
        explain.record(root, rows=t.nrows,
                       wall_s=float(info.get("wall_s", 0.0)),
                       cached=info.get("event") == "hit", rcache=info)
    except Exception:  # noqa: BLE001 - observability never breaks exec
        pass


# --------------------------------------------------------------------------
# module-level singleton + façade (plan/physical.py and the observability
# layers call through these; config.set_config reaches reconfigure())
# --------------------------------------------------------------------------

_cache: Optional[ResultCache] = None
_cache_mu = threading.Lock()

# fleet peering hooks (runtime/fleet.py installs these on gang startup):
# fetch(key) -> payload dict | None asks the fingerprint's previous
# owner for its copy; notify(paths) reports a local mutation-driven
# invalidation for fleet-wide broadcast. Module-level so a test (or a
# fleet teardown) can unhook without touching the cache instance.
_peer_fetch = None
_peer_notify = None


def set_peer_hooks(fetch=None, notify=None) -> None:
    """Install (or clear, with Nones) the fleet peering hooks."""
    global _peer_fetch, _peer_notify
    with _cache_mu:
        _peer_fetch = fetch
        _peer_notify = notify


def peer_export(key):
    """Module façade: host-format payload for a cached query entry."""
    return cache().peer_export(key)


def invalidate_paths(paths) -> int:
    """Module façade: apply a fleet invalidation broadcast."""
    return cache().invalidate_paths(paths)


def cache() -> ResultCache:
    global _cache
    with _cache_mu:
        if _cache is None:
            _cache = ResultCache()
        elif (_cache._owner_pid, _cache._owner_gang) != \
                (_os.getpid(), _gang_id()):
            # ownership changed: the inherited entries hold device
            # buffers (and a governor grant) belonging to the OWNER's
            # gang — serving them here would be silent cross-process
            # sharing. A fleet gang process (its own BODO_TPU_GANG_ID,
            # exported by the controller at spawn) legitimately starts
            # its private cache without noise; a plain fork gets the
            # loud warning.
            gid = _gang_id()
            if not (gid and gid != _cache._owner_gang):
                warnings.warn(
                    f"bodo_tpu result cache: owner changed "
                    f"(pid {_cache._owner_pid} -> {_os.getpid()}, gang "
                    f"{_cache._owner_gang or '-'} -> {gid or '-'}); the "
                    f"cache is per-gang — starting a fresh empty cache. "
                    f"Fleet gang processes should carry their own "
                    f"BODO_TPU_GANG_ID (bodo_tpu.fleet sets this) and "
                    f"share results via the peering tier instead",
                    RuntimeWarning, stacklevel=2)
            _cache = ResultCache()
        return _cache


def node_key(node) -> Optional[Tuple]:
    """Semantic per-node cache key, or None (disabled / unsignable)."""
    if not config.result_cache:
        return None
    try:
        srcs = _sources_of(node)
        if srcs is None:
            return None
        sigs = []
        for kind, ident in srcs:
            s = _source_sig(kind, ident)
            if s is None:
                cache().count("sig_uncacheable")
                return None
            sigs.append((kind, ident, s))
        return ("n", _plan_fp(node), _env_key(),
                _sig_digest(tuple(sigs)))
    except Exception:  # noqa: BLE001 - keying must never fail exec
        return None


def lookup(key):
    return cache().lookup(key)


def record(key, raw, table, wall_s) -> None:
    try:
        cache().record(key, raw, table, wall_s)
    except Exception:  # noqa: BLE001
        pass


def cached_execute(root, run):
    return cache().cached_execute(root, run)


def shed_for_pressure() -> int:
    return cache().shed_for_pressure()


def reconfigure() -> None:
    cache().reconfigure()


def clear() -> None:
    cache().clear()


def stats() -> dict:
    return cache().stats()


def reset_stats() -> None:
    cache().reset_stats()
