"""EXPLAIN ANALYZE: the executed plan tree annotated with observations.

The plan validator knows what a plan SHOULD do and the optimizer what it
WILL do; this module records what it DID: per plan node, the observed
row count vs the estimator's pre-execution guess, result device bytes,
inclusive wall seconds, cache hits, and any adaptive-execution decisions
that fired while the node ran (Postgres' EXPLAIN ANALYZE crossed with
Spark AQE's final-plan annotations).

`physical.execute` assigns every node a stable dotted path ("0", "0.1",
"0.1.0" …) at the start of each query and `record()`s an observation per
node as it completes; AQE-replanned join subtrees get paths re-anchored
under the node they replaced, flagged `replanned`. Observations are
keyed by query id (tracing.query_span) and kept for the last
`_MAX_QUERIES` queries, so `explain_analyze()` after a run renders the
tree of any recent query — `BodoDataFrame.explain_analyze()` is a
thin wrapper over it.

Recording is active only while tracing is on (BODO_TPU_TRACING_LEVEL
>= 1); with tracing off the executor's hot path skips this module
entirely.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional

from bodo_tpu.plan import logical as L

_lock = threading.Lock()
_MAX_QUERIES = 64
# qid -> {"root": Node, "records": {path: record}}
_queries: "OrderedDict[str, dict]" = OrderedDict()
_last_qid: Optional[str] = None


def _qid() -> str:
    from bodo_tpu.utils import tracing
    return tracing.current_query_id() or "-"


def begin_query(root: L.Node, query_id: Optional[str] = None,
                session: Optional[str] = None) -> None:
    """Anchor a query: assign dotted paths over the (optimized) tree and
    open its record store. Called by physical.execute when tracing is
    on. Shared subplans (the optimizer memoizes by key) keep the first
    path they get — later parents see them as cache hits anyway.
    ``session`` tags the query with the serving session that issued it
    (rendered in the EXPLAIN ANALYZE header, carried by slow_queries)."""
    global _last_qid
    qid = query_id or _qid()
    assign_paths(root, "0", force=True)
    with _lock:
        q = _queries.get(qid)
        if q is None:
            q = _queries[qid] = {"root": root, "records": {}}
            while len(_queries) > _MAX_QUERIES:
                _queries.popitem(last=False)
        else:
            q["root"] = root
        if session:
            q["session"] = session
        _last_qid = qid


def query_session(query_id: Optional[str] = None) -> Optional[str]:
    """Serving session a recorded query was tagged with, if any."""
    with _lock:
        qid = query_id or _last_qid
        q = _queries.get(qid) if qid else None
        return q.get("session") if q else None


def assign_paths(node: L.Node, base: str, force: bool = False,
                 replanned: bool = False) -> None:
    """Depth-first dotted-path assignment. `force` overwrites paths
    left over from a previous query's tree walk (plan nodes are reused
    across executions via the session result cache); `replanned` marks
    an AQE-substituted subtree."""
    seen = set()

    def walk(n: L.Node, path: str) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        if force or getattr(n, "_explain_path", None) is None:
            n._explain_path = path
            n._explain_replanned = replanned
        for i, c in enumerate(n.children):
            walk(c, f"{path}.{i}")

    walk(node, base)


def record(node: L.Node, *, rows: int, wall_s: float,
           est_rows: Optional[float] = None,
           bytes: Optional[int] = None, cached: bool = False,
           aqe: Optional[Dict[str, int]] = None,
           mem_peak: Optional[int] = None,
           fusion: Optional[dict] = None,
           comm: Optional[dict] = None,
           xla: Optional[dict] = None,
           rcache: Optional[dict] = None) -> None:
    """One node observation for the current query. Wall seconds are
    INCLUSIVE of the node's children (the executor recurses inside the
    node's span), matching Postgres' actual-time convention. A repeat
    record for the same path keeps the first full execution and only
    bumps its hit count (memoized subplan re-reached). `fusion` carries
    the whole-stage-fusion boundary annotation: for a group root, the
    member ops / compile seconds / cache hit / rows in+out; for an
    interior member, the root path it fused into. `comm` carries the
    comm-observatory delta across the node's execution
    ({wall_s, wait_s, bytes} — inclusive, like wall_s), rendering the
    per-node comm-wait vs compute split. `xla` carries the compile &
    device-memory observatory's delta across the node ({compiles,
    retraces, cause, dispatches, syncs, dev_bytes}) rendered as
    compiled|cached|retraced[cause], the host syncs (`syncs=N`: reads
    of a device value the operator waited on) and the node's net device
    bytes."""
    path = getattr(node, "_explain_path", None)
    if path is None:
        return
    qid = _qid()
    rec = {"path": path, "op": type(node).__name__, "rows": int(rows),
           "wall_s": float(wall_s), "cached": bool(cached), "hits": 1}
    if est_rows is not None:
        rec["est_rows"] = int(est_rows)
    if bytes is not None:
        rec["bytes"] = int(bytes)
    if mem_peak is not None:
        rec["mem_peak"] = int(mem_peak)
    if aqe:
        rec["aqe"] = dict(aqe)
    if fusion:
        rec["fusion"] = dict(fusion)
    if comm:
        rec["comm"] = {k: (round(float(v), 6)
                           if k.endswith("_s") else int(v))
                       for k, v in comm.items()}
    if xla:
        rec["xla"] = dict(xla)
    if rcache:
        rec["rcache"] = dict(rcache)
    if getattr(node, "_explain_replanned", False):
        rec["replanned"] = True
    with _lock:
        q = _queries.get(qid)
        if q is None:
            q = _queries[qid] = {"root": None, "records": {}}
            while len(_queries) > _MAX_QUERIES:
                _queries.popitem(last=False)
        prev = q["records"].get(path)
        if prev is not None and not prev["cached"]:
            prev["hits"] += 1
            # a later record may carry boundary info the first lacked
            # (physical._record_node re-records a fused root with the
            # group annotation attached to the node)
            if fusion and "fusion" not in prev:
                prev["fusion"] = dict(fusion)
            if xla and "xla" not in prev:
                prev["xla"] = dict(xla)
            if rcache and "rcache" not in prev:
                prev["rcache"] = dict(rcache)
            return
        if prev is not None:
            rec["hits"] = prev["hits"] + 1
        q["records"][path] = rec


def _critical_paths(records: Dict[str, dict]) -> set:
    """The dotted paths on the wall-dominant root-to-leaf chain: start
    at the root and descend into the recorded child with the largest
    inclusive wall at every level. With inclusive walls this IS the
    chain that bounds query wall — shaving time anywhere off-chain
    cannot shorten the query. Ties break toward the lowest path index
    (deterministic goldens)."""
    if not records:
        return set()
    root = min(records, key=_pathkey)
    marked = set()
    cur = root
    while True:
        marked.add(cur)
        depth = cur.count(".") + 1
        kids = [p for p in records
                if p.startswith(cur + ".") and p.count(".") == depth]
        if not kids:
            return marked
        cur = max(kids, key=lambda p: (
            records[p]["wall_s"],
            tuple(-x for x in _pathkey(p))))


def critical_path(query_id: Optional[str] = None) -> List[str]:
    """Dotted paths of the query's critical chain, root first."""
    with _lock:
        qid = query_id or _last_qid
        q = _queries.get(qid) if qid else None
        records = dict(q["records"]) if q else {}
    return sorted(_critical_paths(records), key=_pathkey)


def last_query_id() -> Optional[str]:
    with _lock:
        return _last_qid


def slow_queries(n: int = 5) -> List[dict]:
    """The slowest-N recorded queries, each with its wall seconds and
    rendered EXPLAIN ANALYZE tree — the flight recorder embeds these so
    a post-mortem shows what the engine was busy with before it died.
    Wall time prefers the query span; a query recorded without a span
    falls back to its slowest (inclusive) node observation."""
    from bodo_tpu.utils import tracing
    with _lock:
        qids = list(_queries.keys())
    scored = []
    for qid in qids:
        wall = tracing.query_wall_s(qid)
        if wall is None:
            with _lock:
                q = _queries.get(qid)
                recs = list(q["records"].values()) if q else []
            wall = max((r["wall_s"] for r in recs), default=0.0)
        scored.append((float(wall), qid))
    scored.sort(key=lambda t: -t[0])
    out = []
    for wall, qid in scored[:max(0, int(n))]:
        row = {"query_id": qid, "wall_s": round(wall, 6),
               "explain": explain_analyze(qid)}
        sid = query_session(qid)
        if sid:
            row["session"] = sid
        out.append(row)
    return out


def reset() -> None:
    global _last_qid
    with _lock:
        _queries.clear()
        _last_qid = None


def _pathkey(path: str):
    return tuple(int(p) for p in path.split("."))


def _fmt_bytes(n: int) -> str:
    v = float(n)
    for unit in ("B", "KB", "MB", "GB"):
        if v < 1024 or unit == "GB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024
    return f"{v:.1f}GB"  # pragma: no cover


def _node_label(node: L.Node) -> str:
    if isinstance(node, L.ReadParquet):
        return f"ReadParquet({node.path})"
    if isinstance(node, L.ReadCsv):
        return f"ReadCsv({node.path})"
    if isinstance(node, L.Join):
        return f"Join({node.how}, on={list(node.left_on)})"
    if isinstance(node, L.Aggregate):
        return f"Aggregate(keys={list(node.keys)})"
    if isinstance(node, L.Filter):
        return "Filter"
    if isinstance(node, L.Sort):
        return f"Sort(by={list(node.by)})"
    if isinstance(node, L.Limit):
        return f"Limit({node.n})"
    return type(node).__name__


def _annotate(rec: Optional[dict]) -> str:
    if rec is None:
        return "(not executed)"
    parts = [f"rows={rec['rows']}"]
    if "est_rows" in rec:
        parts.append(f"est={rec['est_rows']}")
    if "bytes" in rec:
        parts.append(f"bytes={_fmt_bytes(rec['bytes'])}")
    if "mem_peak" in rec:
        parts.append(f"mem_peak={_fmt_bytes(rec['mem_peak'])}")
    parts.append(f"wall={rec['wall_s']:.3f}s")
    c = rec.get("comm")
    if c:
        # comm-wait vs compute split: comm wall (transfer+wait) out of
        # the node's inclusive wall, with the peer-wait share inside it
        compute = max(rec["wall_s"] - c.get("wall_s", 0.0), 0.0)
        bit = (f"comm={c.get('wall_s', 0.0):.3f}s"
               f"/compute={compute:.3f}s")
        if c.get("wait_s"):
            bit += f" (wait={c['wait_s']:.3f}s)"
        parts.append(bit)
    if rec.get("aqe"):
        decs = ",".join(f"{k}x{v}" if v > 1 else k
                        for k, v in sorted(rec["aqe"].items()))
        parts.append(f"aqe=[{decs}]")
    f = rec.get("fusion")
    if f:
        if "fused_into" in f:
            parts.append(f"fused->{f['fused_into']}")
        else:
            bits = [f"{len(f.get('members', ()))} ops",
                    "cache_hit" if f.get("cache_hit") else "compiled"]
            if f.get("compile_s"):
                bits.append(f"compile={f['compile_s']:.3f}s")
            if "rows_in" in f:
                bits.append(f"rows_in={f['rows_in']}")
            parts.append(f"fused[{', '.join(bits)}]")
    x = rec.get("xla")
    if x:
        bits = []
        if x.get("retraces"):
            cause = x.get("cause") or "unknown"
            bits.append(f"retraced[{cause}]")
        elif x.get("compiles"):
            bits.append("compiled")
        elif x.get("dispatches"):
            bits.append("cached")
        if x.get("syncs"):
            bits.append(f"syncs={x['syncs']}")
        if bits:
            parts.append("xla=" + ",".join(bits))
        db = x.get("dev_bytes")
        if db:
            sign = "+" if db > 0 else "-"
            parts.append(f"dev={sign}{_fmt_bytes(abs(int(db)))}")
    rc = rec.get("rcache")
    if rc:
        bits = [rc.get("event", "hit")]
        if rc.get("delta_files"):
            bits.append(f"delta_files={rc['delta_files']}")
        if rc.get("saved_s"):
            bits.append(f"saved={rc['saved_s']:.3f}s")
        parts.append(f"result_cache[{', '.join(bits)}]")
    if rec.get("replanned"):
        parts.append("replanned")
    if rec.get("cached"):
        parts.append("cached")
    if rec.get("hits", 1) > 1:
        parts.append(f"hits={rec['hits']}")
    if rec.get("critical"):
        parts.append("on critical path")
    return "  ".join(parts)


def explain_analyze(query_id: Optional[str] = None) -> str:
    """Render the executed plan tree of a query (default: the last one
    executed) with per-node observations. Returns a diagnostic string
    when the query is unknown or was run without tracing."""
    from bodo_tpu.utils import tracing
    with _lock:
        qid = query_id or _last_qid
        q = _queries.get(qid) if qid else None
        root = q["root"] if q else None
        session = q.get("session") if q else None
        records = {p: dict(r) for p, r in q["records"].items()} if q \
            else {}
    if qid is None or q is None:
        return ("EXPLAIN ANALYZE: no recorded query "
                "(run with tracing_level >= 1)")
    for p in _critical_paths(records):
        records[p]["critical"] = True
    lines = []
    wall = tracing.query_wall_s(qid)
    if wall is None and records:
        wall = max(r["wall_s"] for r in records.values())
    header = f"EXPLAIN ANALYZE  query={qid}"
    if session:
        header += f"  session={session}"
    if wall is not None:
        header += f"  wall={wall:.3f}s"
    lines.append(header)
    if root is None:
        for rec in sorted(records.values(),
                          key=lambda r: _pathkey(r["path"])):
            lines.append(f"[{rec['path']}] {rec['op']}  {_annotate(rec)}")
        return "\n".join(lines)

    def walk(n: L.Node, prefix: str, tail: bool, top: bool) -> None:
        path = getattr(n, "_explain_path", None)
        rec = records.get(path) if path else None
        conn = "" if top else ("└─ " if tail else "├─ ")
        lines.append(f"{prefix}{conn}{_node_label(n)} [{path}]  "
                     f"{_annotate(rec)}")
        child_prefix = prefix if top else \
            prefix + ("   " if tail else "│  ")
        kids = list(n.children)
        for i, c in enumerate(kids):
            walk(c, child_prefix, i == len(kids) - 1, False)

    walk(root, "", True, True)
    return "\n".join(lines)
