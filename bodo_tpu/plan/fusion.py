"""Whole-stage fusion: adjacent plan stages compiled into ONE program.

Without fusion every relational op dispatches its own jitted kernel
with a host round-trip between plan nodes: a filter compacts its rows,
syncs the surviving count to the host, re-buckets, and only then does
the next projection or aggregate trace over the materialized
intermediate. The per-stage count syncs and intermediate buffers are
the flat tax the hot profiles show across the taxi/TPC-H
pipelines — the same observation that drives XLA whole-program fusion
in JAX and HPAT's whole-function parallel compilation: adjacent
operators should compile together so intermediates never materialize.

This module implements the plan-level version of that inversion:

  group formation   `plan_fusion_groups` walks the optimized plan and
                    greedily claims maximal chains of pipeline-
                    compatible nodes — [Filter|Projection]+ with an
                    optional dense-aggregate root — into FusionGroups.
                    Interior members must be single-parent and
                    unmaterialized (a shared or cached subplan keeps
                    its own dispatch so other consumers still hit it).

  fused body        inside the compiled program the chain is LAZY: the
                    column tree and a row-validity mask travel through
                    the steps together. Filters AND into the mask (no
                    per-filter compaction, no count sync); projections
                    evaluate element-wise on the uncompacted tree (dead
                    rows compute garbage harmlessly, exactly like the
                    eval-then-mask order of relational.filter_table).
                    One `K.compact` runs at group exit (the surviving
                    rows' source index found once, every column
                    gathered at it) — or ZERO when a
                    terminal dense Aggregate consumes the mask directly
                    via `relational.dense_agg_tail`, which aggregates
                    per slot by one of three routes
                    (`relational.dense_route`): the MXU one-hot-matmul
                    accumulate (`ops/pallas_kernels.dense_accumulate`)
                    when its gate admits it, masked reductions over
                    the rows when the slot space is a handful
                    (`DENSE_REDUCE_MAX_SLOTS`), `segment_*` scatters
                    otherwise. The `fused_group` span carries the
                    route as `dense_route`; `stats()` counts by it.

  route spans       a join and a group-by each open one span around
                    the realisation they took, named for it
                    (`join_route`: `join.dense|hash|sort|fused`;
                    `groupby_route`: `groupby.dense|packed|hashed|
                    sort|fused`), and `stats()` counts the same under
                    `join_<route>` / `groupby_<route>`. A trace's
                    readers see names only, so the route is the name.
                    A LUT join built on its left side says so in the
                    span's argument `build` (`join_build_left`), and
                    a build side refused unbuilt because its keys must
                    repeat is counted (`join_build_skipped`).
                    On a mesh a join also says how its rows crossed
                    chips (`exchange`): `exchange.shuffle` around the
                    two `shuffle_by_key` of a join of sharded sides,
                    `exchange.broadcast` around the replication of a
                    build side, and the counter `exchange_inprogram`
                    for a fused group's in-program `all_gather`.

  sharding          derived from the shardcheck REP/DIST lattice
                    (`analysis/plan_validator.check_fusion_boundary`
                    cross-checks the runtime input against it): REP
                    input -> plain `jax.jit`; 1D input -> the program
                    wraps in `shard_map` with explicit P(data_axis)
                    in/out specs, one count sync for the WHOLE group.
                    A fused terminal aggregate requires REP input; over
                    1D input the group degrades to partial fusion (the
                    chain fuses, `relational.groupby_agg` finishes).

  donation          on accelerator backends the input tree is donated
                    (`donate_argnums`) when the input node is the
                    group's only consumer and not user-owned
                    (FromPandas buffers belong to the caller), so even
                    the group input buffer is recycled in-program.

  caching           compiled groups live in a FusionProgramCache keyed
                    by the group signature (op sequence + input
                    schema/dictionary fingerprints + distribution +
                    agg spec); compile time feeds the shared
                    bodo_tpu_jit_compile_seconds histogram.

  observability     the group root records a `fusion` annotation in
                    EXPLAIN ANALYZE (member ops, cache hit, compile
                    seconds, rows in/out); interior members record a
                    `fused->root` marker. AQE stage-boundary
                    observation still fires at the group edge (the
                    root's result is a normal stage result).

  lockstep          collectives fused INSIDE a program can no longer
                    fingerprint per-op at dispatch, so each compiled
                    group registers a manifest with
                    `analysis/lockstep.register_fusion_manifest` and a
                    multi-shard dispatch is sequence-numbered as ONE
                    composite collective via `lockstep.pre_fused`.

Failure policy (the chaos-test contract): build/trace-time problems —
unfusable expression shapes, schema walk failures, trace errors that
are neither OOM nor degradable — fall back silently to per-node
execution and negative-cache the group signature. RUNTIME dispatch
errors propagate so `physical._exec_with_oom_retry` and
`physical._try_degrade` classify them exactly as they would an unfused
stage; under a degraded (force-replicated) re-run the group gathers
its 1D input and re-dispatches the REP program.

Join-probe and shuffle boundaries fuse too: `plan_fusion_groups`
tries `plan/fusion_join.try_join_group` first, so a
[chain -> Join -> chain -> agg] region compiles into one program with
the hash-probe (against a device-resident cached build table) and —
for 1D probes with a terminal decomposable aggregate — the
partial-agg hash shuffle (`lax.all_to_all`) traced INSIDE the
shard_map body. See plan/fusion_join.py.

Disable with `BODO_TPU_FUSION=0` / `set_config(fusion=False)`; the
process-wide compile budget (`BODO_TPU_FUSION_MAX_COMPILES`) bounds
how many distinct programs one process may pin before new signatures
run unfused.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bodo_tpu.analysis import lockstep
from bodo_tpu.analysis import progcheck
from bodo_tpu.config import config
from bodo_tpu.ops import kernels as K
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan import expr as E
from bodo_tpu.plan import logical as L
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import Column, ONED, REP, Table
from bodo_tpu.runtime import xla_observatory as xobs
from bodo_tpu.utils.kernel_cache import (KERNEL_CACHE_SIZE,
                                         FusionProgramCache, named_jit)
from bodo_tpu.utils import tracing
from bodo_tpu.utils.logging import log

# NOTE: bodo_tpu.relational imports this module at module level (for
# @fusion_stage), so relational/physical/shuffle may only be imported
# INSIDE functions here — a module-level import would cycle.

def _describe_sig(key):
    """Split a fusion-program signature into named facets so the
    program registry can attribute a retrace to the facet that changed
    (mesh vs schema vs plan steps vs donation flag)."""
    base = key[0]
    if base == "fusedchain" and len(key) == 6:
        _, mesh, schema, steps, dist, donate = key
        return base, {"mesh": xobs._short(mesh),
                      "schema": xobs._short(schema),
                      "dtype": tuple(c[1] for c in schema),
                      "steps": xobs._short(steps), "dist": dist,
                      "donate": bool(donate)}
    if base == "fusedagg" and len(key) == 9:
        (_, schema, steps, kn, aggs, sizes, los, use_mxu,
         donate) = key
        return base, {"schema": xobs._short(schema),
                      "dtype": tuple(c[1] for c in schema),
                      "steps": xobs._short((steps, kn, aggs)),
                      "shape": tuple(sizes),
                      "static": (xobs._short(los), bool(use_mxu)),
                      "donate": bool(donate)}
    return str(base), xobs.facets_from_sig(key)


_programs = FusionProgramCache(maxsize=KERNEL_CACHE_SIZE,
                               subsystem="fusion",
                               describe=_describe_sig)

_stats = {"groups_planned": 0, "groups_executed": 0, "stream_chains": 0,
          "partial_agg": 0, "fallbacks": 0, "donated": 0,
          "budget_spent": 0,
          # fused terminal aggregates by relational.dense_route
          "dense_reduce": 0, "dense_scatter": 0, "dense_mxu": 0,
          # joins by the realisation they took (`join_route`)
          "join_dense": 0, "join_hash": 0, "join_sort": 0, "join_fused": 0,
          # inner joins realised with the sides exchanged (the LUT built
          # on the left), and builds `relational.keys_must_repeat`
          # refused before any program ran
          "join_build_left": 0, "join_build_skipped": 0,
          # LUT joins (dense or hash) probed by a sharded side, once a
          # shard under `shard_map`, against a LUT built on a replicated
          # side
          "join_lut_sharded": 0,
          # inner LUT joins by how their result was emitted
          # (`join_emitted`): compacted at its own size, or not at all
          "join_emit": 0, "join_emit_skipped": 0,
          # how a join's rows crossed chips (`exchange`): both sides
          # hash-shuffled, a build side replicated, or a fused group's
          # build gathered inside its program (`exchange_inprogram`)
          "exchange_shuffle": 0, "exchange_broadcast": 0,
          "exchange_inprogram": 0,
          # runs of a sharded join again after its output bucket
          # overflowed, at the exact count (`join_overflow_retried`)
          "join_overflow_retries": 0,
          # group-bys by the realisation they took (`groupby_route`)
          "groupby_dense": 0, "groupby_packed": 0, "groupby_hashed": 0,
          "groupby_sort": 0, "groupby_fused": 0,
          # scan batches entering fused chains straight off the device
          # decode path (io/device_decode.py) — no host round-trip
          # between ingest and the compiled chain body
          "device_scan_batches": 0}

# structural signatures whose trace failed: don't re-trace every query
_failed: set = set()

# XLA:CPU's JIT crashes once a process pins thousands of distinct
# compiled executables (the leak runtests.py works around by grouping
# test modules into subprocesses). Fusion programs draw from the same
# pool on top of the per-op kernels, so new-signature compiles stop
# after a process-wide budget; later groups run unfused, which is
# always correct. <0 disables the budget.
_max_compiles = int(os.environ.get("BODO_TPU_FUSION_MAX_COMPILES",
                                   "128"))
_n_compiles = 0


def _budget_compile(sig) -> None:
    """Consume one unit of the process-wide fusion compile budget, or
    raise FusionFallback once it is spent. When
    BODO_TPU_FUSION_COMPILE_LOG names a file, the signature is appended
    before the compile — the log survives an XLA compiler crash, which
    in-process stats do not."""
    global _n_compiles
    if _n_compiles >= _max_compiles >= 0 \
            or not xobs.try_spend("fusion"):
        _stats["budget_spent"] += 1
        raise FusionFallback("fusion compile budget spent")
    _n_compiles += 1
    path = os.environ.get("BODO_TPU_FUSION_COMPILE_LOG")
    if path:
        with open(path, "a") as f:
            f.write(repr(sig)[:500] + "\n")


def stats() -> dict:
    out = dict(_stats)
    out.update(_programs.stats())
    return out


def join_route(route: str, keys: int, rows_left: int, rows_right: int,
               build_left: bool = False, sharded_probe: bool = False):
    """The span a join opens around the realisation it took, once the
    route is settled (a try that gives up at its build opens none, so a
    join has one; a hash or fused probe that comes back unresolved, the
    pathological case, leaves its span behind the sort's): `join.dense`
    (dense LUT), `join.hash` (hash LUT), `join.sort` (`join_local` and
    its sorts, replicated, sharded or broadcast) or `join.fused` (the
    probe inside a fused join group's program). A trace's readers see a
    span's name only, so the route is in the name; `stats()` counts the
    same under `join_<route>`. `rows_left` and `rows_right` are the
    join's own sides whichever was built on. A LUT route builds on the
    right and probes with the left; `build_left` says that
    `relational.join_tables` exchanged them for an inner join (LUT on
    the left, probed by the right): the span then carries the argument
    `build="left"` and `stats()` counts it under `join_build_left`.
    `sharded_probe` says that a LUT route's probe side is row-sharded
    and probes the LUT of a replicated build once a shard under
    `shard_map`: the argument `probe="sharded"`, counted under
    `join_lut_sharded`."""
    _stats["join_" + route] += 1
    _stats["join_build_left"] += build_left
    _stats["join_lut_sharded"] += sharded_probe
    args = {}
    if build_left:
        args["build"] = "left"
    if sharded_probe:
        args["probe"] = "sharded"
    return tracing.event("join." + route, keys=keys, rows_left=rows_left,
                         rows_right=rows_right, **args)


def groupby_route(route: str, keys: int, rows_in: int, slots: int = 0,
                  **args):
    """The span a group-by opens around the realisation it took, once
    the route is settled: `groupby.dense` (one slot a key combination,
    `relational._groupby_agg_dense`), `groupby.packed` (the keys packed
    into one int64; the group-by over that key opens a span of its own
    inside), `groupby.hashed` (the scatter-claim table; a claim that
    comes back unresolved, the pathological case, leaves its span
    behind the sort's), `groupby.sort` (`groupby_local`: every row
    sorted by the keys) on a replicated table, or `groupby.fused` (the
    terminal aggregate of a fused group, `_run_fused_agg`). `slots` is
    the slot space of a dense realisation, 0 elsewhere; `args` ride
    along (the `dense_route` of a dense or fused one). As with
    `join_route` the route is in the name, and `stats()` counts the
    same under `groupby_<route>`."""
    _stats["groupby_" + route] += 1
    return tracing.event("groupby." + route, keys=keys, rows_in=rows_in,
                         slots=slots, **args)


def join_emitted(rows_out: int, skipped: bool) -> None:
    """An inner LUT join (`join.dense`, `join.hash`) says, inside its
    span, how many rows it found and whether emitting them had to
    compact (`join_emit`) or every live probe row had its match and the
    compaction was skipped (`join_emit_skipped`)."""
    _stats["join_emit_skipped" if skipped else "join_emit"] += 1
    tracing.annotate(rows_out=rows_out)


def exchange(kind: str, **args):
    """The span a join on a mesh opens around what moves its rows
    between chips, counted under `exchange_<kind>`:
    `exchange.shuffle` (`keys`, `rows_left`, `rows_right`) around the
    pair of `shuffle_by_key` calls that co-locate equal keys of two
    sharded sides, `exchange.broadcast` (`rows`) around the replication
    of a build side (`Table.gather` of a sharded one: device to host, a
    numpy repack, host to device; nothing to do for a side that is
    replicated already, whose span says only that it is). A fused join
    group that gathers its build inside the program (`lax.all_gather`
    under `shard_map`) has no host span of its own: it counts
    `exchange_inprogram` (`exchange_inprogram()`) and says so on the
    group's span."""
    _stats["exchange_" + kind] += 1
    return tracing.event("exchange." + kind, **args)


def exchange_inprogram(rows: int) -> None:
    """A fused join group whose build side crosses chips by
    `lax.all_gather` inside its program: counted, and written as the
    arguments `exchange` and `build_rows` of the group's span."""
    _stats["exchange_inprogram"] += 1
    tracing.annotate(exchange="inprogram", build_rows=rows)


def join_overflow_retried(retries: int) -> None:
    """A sharded join (`relational._join_sharded`) whose first output
    bucket, twice a probe shard's capacity, overflowed and which ran
    again at the exact count: counted under `join_overflow_retries`, and
    written as the argument `overflow_retries` of the join's span."""
    _stats["join_overflow_retries"] += retries
    tracing.annotate(overflow_retries=retries)


def join_build_skipped() -> None:
    """A build side that `relational.keys_must_repeat` refused: more
    rows than its keys can take values, so neither LUT was built on it
    and no program ran to find the duplicates."""
    _stats["join_build_skipped"] += 1


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0
    _programs.reset_stats()
    _failed.clear()


def clear_programs() -> None:
    """Drop every cached fusion program and return its compile budget:
    releasing the program references is what frees the underlying
    executables, so a caller starting from an empty cache (tests,
    long-lived sessions recycling state) gets the full budget back."""
    global _n_compiles
    _programs.clear()
    _n_compiles = 0
    xobs.reset_budget("fusion")


class FusionFallback(Exception):
    """Internal control flow: this group/chain cannot fuse (build or
    trace failure) — the caller falls back to per-node execution.
    Never escapes the fusion layer."""


def fusion_stage(fn):
    """Mark a function as a fusion-eligible traced stage body: it runs
    (or may run) INSIDE a compiled fusion program, where host sync —
    `jax.device_get`, `.to_pandas()`, `block_until_ready` — is illegal.
    The shardcheck `fusion-host-call` lint rule audits every function
    carrying this decorator."""
    fn.__fusion_stage__ = True
    return fn


# ---------------------------------------------------------------------------
# fusability gates
# ---------------------------------------------------------------------------

# expressions whose evaluation is host-side by construction (dictionary
# rewrites / host formatting in relational.assign_columns) — they can
# never run inside a compiled body
_HOST_EXPRS = (E.DictMap, E.ToChar, E.StrConcat, E.StrToList, E.NestedFn)


def _expr_fusable(e: E.Expr, schema) -> bool:
    """Can `e` evaluate inside a fused body with plain eval_expr?
    String-PRODUCING outputs are only fusable as bare column passthrough
    (the dictionary re-attaches host-side from the input column);
    string-CONSUMING nodes (StrPredicate/StrLen/...) bake their host
    LUT at trace time and are fine."""
    if E.contains_expr(e, _HOST_EXPRS):
        return False
    if isinstance(e, E.CodeLUT) or E.codelut_misplaced(e):
        return False
    try:
        d = E.infer_dtype(e, schema)
    except Exception:  # noqa: BLE001 - unknown shape -> not fusable
        return False
    if d is dt.STRING and not isinstance(e, E.ColRef):
        return False
    if getattr(d, "kind", "") in ("list", "struct", "map") and \
            not isinstance(e, E.ColRef):
        return False
    return True


def _node_fusable(node: L.Node) -> bool:
    if isinstance(node, L.Filter):
        return _expr_fusable(node.predicate, node.child.schema)
    if isinstance(node, L.Projection):
        return all(_expr_fusable(e, node.child.schema)
                   for _, e in node.exprs)
    return False


# ops the dense aggregate tail cannot finish in one segment pass
_UNFUSABLE_AGG = ("nunique", "mode", "median")


def _agg_fusable(node: L.Aggregate) -> bool:
    if not node.keys:
        return False
    for _, op, _ in node.aggs:
        if op in _UNFUSABLE_AGG or op.startswith(("q:", "quantile_",
                                                  "listagg")):
            return False
    return True


# ---------------------------------------------------------------------------
# group formation
# ---------------------------------------------------------------------------

class FusionGroup:
    """One fusable region of the plan.

    chain    [Filter|Projection] members, BOTTOM-UP (chain[0] consumes
             the input node's table)
    agg      optional terminal Aggregate (the group root when present)
    root     the member whose _exec dispatch runs the whole group
    input    the plan node below the group (executed normally)
    donate_ok  the input node has no consumer outside this group and
             its buffers are engine-owned (not FromPandas) — the
             compiled program may donate them
    """

    __slots__ = ("chain", "agg", "root", "input", "donate_ok")

    def __init__(self, chain, agg, input_node, donate_ok):
        self.chain = list(chain)
        self.agg = agg
        self.root = agg if agg is not None else self.chain[-1]
        self.input = input_node
        self.donate_ok = bool(donate_ok)

    @property
    def members(self):
        """Members root-first (display order)."""
        out = ([self.agg] if self.agg is not None else [])
        out.extend(reversed(self.chain))
        return out

    def member_ops(self) -> Tuple[str, ...]:
        return tuple(type(m).__name__ for m in self.members)


def plan_fusion_groups(root: L.Node) -> List[FusionGroup]:
    """Annotate the (optimized) plan with fusion groups and return
    them. Clears stale annotations from prior executions on EVERY node
    first — plan nodes are reused across queries via the session result
    cache, and a leftover group from a differently-shaped walk must
    never dispatch."""
    nodes: List[L.Node] = []
    seen = set()
    stack = [root]
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        nodes.append(n)
        stack.extend(n.children)
    parents: Dict[int, int] = {}
    for n in nodes:
        n._fusion_group = None
        n._fusion_info = None
        for c in n.children:
            parents[id(c)] = parents.get(id(c), 0) + 1
    if not config.fusion:
        return []
    groups: List[FusionGroup] = []
    claimed = set()
    for n in nodes:  # roots precede their descendants (DFS preorder)
        if id(n) in claimed:
            continue
        g = None
        if config.fusion_join:
            # join groups first: a [chain -> Join -> chain -> agg]
            # region fuses across the join-probe boundary
            # (plan/fusion_join.py); the plain chain grouper below
            # would otherwise claim the above-join chain for itself
            from bodo_tpu.plan import fusion_join
            g = fusion_join.try_join_group(n, parents, claimed)
        if g is None:
            g = _try_group(n, parents)
        if g is None:
            continue
        for m in g.members:
            claimed.add(id(m))
        n._fusion_group = g
        groups.append(g)
    _stats["groups_planned"] += len(groups)
    return groups


def _try_group(node: L.Node, parents) -> Optional[FusionGroup]:
    agg = None
    top = node
    if isinstance(node, L.Aggregate) and _agg_fusable(node) and \
            node._cached is None:
        agg = node
        top = node.child
        # the chain below an agg root is interior: single-parent,
        # unmaterialized
        if parents.get(id(top), 0) != 1 or top._cached is not None:
            return None
    chain_td: List[L.Node] = []  # top-down while walking
    cur = top
    while isinstance(cur, (L.Filter, L.Projection)) and \
            cur._cached is None and _node_fusable(cur):
        if cur is not node and parents.get(id(cur), 0) != 1:
            break  # interior member shared by another parent
        chain_td.append(cur)
        cur = cur.child
    if agg is not None:
        if not chain_td:
            return None  # bare aggregate: nothing to fuse with
    elif len(chain_td) < 2:
        return None  # a lone filter/projection fuses nothing
    input_node = cur
    donate_ok = (parents.get(id(input_node), 0) == 1
                 and not isinstance(input_node, L.FromPandas))
    return FusionGroup(list(reversed(chain_td)), agg, input_node,
                       donate_ok)


def stream_chain(node: L.Node):
    """Maximal fusable [Filter|Projection]+ chain rooted at `node` for
    the streaming executors' per-batch bodies. Returns (steps bottom-up,
    source node) or None when fewer than two stages fuse. Unlike plan
    groups, materialization/sharing is irrelevant: the streaming
    compiler already recomputes these stages per batch."""
    if not config.fusion:
        return None
    chain_td: List[L.Node] = []
    cur = node
    while isinstance(cur, (L.Filter, L.Projection)) and _node_fusable(cur):
        chain_td.append(cur)
        cur = cur.child
    if len(chain_td) < 2:
        return None
    return list(reversed(chain_td)), cur


# ---------------------------------------------------------------------------
# host-side metadata walk
# ---------------------------------------------------------------------------

def _subst(e: E.Expr, mapping: Dict[str, E.Expr]) -> E.Expr:
    """Substitute ColRefs through `mapping` (generic walk over the
    frozen Expr dataclasses) — composes a chain step's expression back
    into an expression over the group INPUT schema, which is what
    expr_range and the dense-agg key planner reason over."""
    if isinstance(e, E.ColRef):
        return mapping[e.name]
    if not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, E.Expr):
            nv = _subst(v, mapping)
            if nv is not v:
                changes[f.name] = nv
        elif isinstance(v, tuple) and any(isinstance(x, E.Expr)
                                          for x in v):
            changes[f.name] = tuple(
                _subst(x, mapping) if isinstance(x, E.Expr) else x
                for x in v)
    return dataclasses.replace(e, **changes) if changes else e


def _chain_meta(t: Table, steps):
    """Walk the chain on host: per-step (kind, payload, schema, dicts)
    snapshots for the traced body, plus the final column order, schema,
    dictionaries and input-composed expressions (for vrange/agg-range
    derivation)."""
    schema = {n: c.dtype for n, c in t.columns.items()}
    dicts = {n: c.dictionary for n, c in t.columns.items()
             if c.dictionary is not None}
    return _chain_meta_from(schema, dicts, steps)


def _chain_meta_from(schema, dicts, steps):
    """Schema-level `_chain_meta`: the fused-join planner
    (plan/fusion_join.py) walks the ABOVE-join chain over the JOINED
    schema, which exists only as names/dtypes/dictionaries at plan time
    — there is no host Table to hand to `_chain_meta`."""
    schema = dict(schema)
    dicts = dict(dicts)
    compose: Dict[str, E.Expr] = {n: E.ColRef(n) for n in schema}
    meta = []
    for s in steps:
        if isinstance(s, L.Filter):
            meta.append(("filter", s.predicate, dict(schema), dict(dicts)))
        else:
            meta.append(("project", tuple(s.exprs), dict(schema),
                         dict(dicts)))
            ns: Dict[str, dt.DType] = {}
            ndic: Dict[str, np.ndarray] = {}
            ncomp: Dict[str, E.Expr] = {}
            for n, e in s.exprs:
                d = E.infer_dtype(e, schema)
                ns[n] = d
                if isinstance(e, E.ColRef) and e.name in dicts:
                    ndic[n] = dicts[e.name]
                ncomp[n] = _subst(e, compose)
            schema, dicts, compose = ns, ndic, ncomp
    return meta, list(schema), schema, dicts, compose


def _steps_sig(steps) -> Tuple:
    out = []
    for s in steps:
        if isinstance(s, L.Filter):
            out.append(("filter", s.predicate.key()))
        else:
            out.append(("project",
                        tuple((n, e.key()) for n, e in s.exprs)))
    return tuple(out)


def _struct_sig(t: Table) -> Tuple:
    """Cross-rank-stable input signature for the lockstep group
    fingerprint: relational._sig's dictionary fingerprints use python
    hash() (randomized per process), so they are per-process cache
    detail, not identity."""
    return tuple((n, c.dtype.name, c.valid is not None)
                 for n, c in t.columns.items())


def _group_fp(fp_sig) -> str:
    """12-hex structural fingerprint, identical on every rank for the
    same plan shape (sha1, not hash(): python hashing is seeded)."""
    return hashlib.sha1(repr(fp_sig).encode()).hexdigest()[:12]


# ---------------------------------------------------------------------------
# traced bodies
# ---------------------------------------------------------------------------

@fusion_stage
def _chain_body(meta, in_names, tree, count):
    """Traced fused-chain body: carry (tree, mask) through the steps
    lazily — filters AND into the mask without compacting, projections
    evaluate element-wise on the uncompacted tree. Returns the final
    column tree and the live-row mask; the caller decides whether to
    compact (chain exit) or feed the mask to the dense aggregate tail
    (zero compactions)."""
    cap = tree[in_names[0]][0].shape[0]
    mask = K.row_mask(count, cap)
    return _chain_body_masked(meta, tree, mask)


@fusion_stage
def _chain_body_masked(meta, tree, mask):
    """`_chain_body` with a caller-supplied initial mask: fused-join
    programs thread the probe side's live-row mask (already ANDed with
    the join hit mask for inner joins) into the above-join chain, so
    the whole below-chain -> probe -> above-chain region shares ONE
    lazy (tree, mask) carry and at most one compaction."""
    cap = mask.shape[0]
    cur = dict(tree)
    for kind, payload, schema, dicts in meta:
        if kind == "filter":
            d, v = E.eval_expr(payload, cur, dicts, schema)
            if v is not None:
                d = d & v
            mask = mask & d
        else:
            new = {}
            for n, e in payload:
                if isinstance(e, E.ColRef):
                    new[n] = cur[e.name]
                    continue
                d, v = E.eval_expr(e, cur, dicts, schema)
                if d.ndim == 0:  # literal projection -> broadcast
                    d = jnp.broadcast_to(d, (cap,))
                new[n] = (d, v)
            cur = new
    return cur, mask


def _compile_chain(meta, in_names, out_names):
    """REP fused-chain program: (tree, count) -> (flat pairs, count).
    Outputs are POSITIONAL (out_names order) — dict pytrees come back
    from jit alphabetized, and a fused group must not silently reorder
    the root's schema."""

    has_filter = any(kind == "filter" for kind, _, _, _ in meta)

    def fused(tree, count):
        cur, mask = _chain_body(meta, in_names, tree, count)
        flat = []
        for n in out_names:
            d, v = cur[n]
            flat.append(d)
            flat.append(v)
        if not has_filter:
            # projection-only chain: the mask is still the trivial row
            # mask, so compaction would be a full-table copy the unfused
            # path never pays — pass the columns through untouched
            return tuple(flat), count
        out, cnt = K.compact(mask, tuple(flat))
        return out, cnt

    return fused


# ---------------------------------------------------------------------------
# chain execution (shared by plan groups and streaming batches)
# ---------------------------------------------------------------------------

def _run_chain(t: Table, steps, donate: bool = False) -> Table:
    """Dispatch the fused [Filter|Projection]+ chain over `t` as one
    compiled program (REP: jit; 1D: shard_map with one count sync).
    Raises FusionFallback on build/trace failure; runtime errors
    propagate for the resilience envelope."""
    from bodo_tpu import relational as R

    if not t.names:
        raise FusionFallback("empty schema")
    fp_sig = ("fusedchain", _struct_sig(t), _steps_sig(steps),
              t.distribution)
    if fp_sig in _failed:
        raise FusionFallback("negative-cached")
    try:
        meta, out_names, out_schema, out_dicts, compose = \
            _chain_meta(t, steps)
    except Exception as e:  # noqa: BLE001 - build failure -> unfused
        _failed.add(fp_sig)
        raise FusionFallback(str(e)) from e
    # Filter-less chains never change row alignment, so any output that
    # composes to a bare input ColRef can alias the input column around
    # the program — returning it from jit would make XLA copy the whole
    # buffer, a cost the unfused path never pays on passthroughs.
    has_filter = any(k == "filter" for k, _, _, _ in meta)
    passthrough: Dict[str, str] = {}
    if not has_filter:
        for n in out_names:
            ce = compose.get(n)
            if isinstance(ce, E.ColRef) and ce.name in t.columns:
                passthrough[n] = ce.name
    jit_names = [n for n in out_names if n not in passthrough]
    if not jit_names:
        # pure rename/reorder chain: no device work at all
        cols = {n: t.columns[passthrough[n]] for n in out_names}
        res = Table(cols, t.nrows, t.distribution, t.counts)
        res._fusion_compiled = False  # type: ignore[attr-defined]
        res._fusion_compile_s = 0.0  # type: ignore[attr-defined]
        res._fusion_donated = False  # type: ignore[attr-defined]
        return R.rebucket(res)
    m = mesh_mod.get_mesh()
    from bodo_tpu.parallel.shuffle import _mesh_key
    # donation is only sound when compaction makes fresh output buffers;
    # a filter-less chain aliases passthrough inputs into its output
    donate = bool(donate) and has_filter and t.distribution == REP and \
        jax.default_backend() in ("tpu", "gpu")
    sig = ("fusedchain", _mesh_key(m), R._sig(t), _steps_sig(steps),
           t.distribution, donate)
    fp = _group_fp(fp_sig)
    fn = _programs.lookup(sig)
    compiled = fn is None
    if compiled:
        _budget_compile(sig)
        in_names = list(t.names)
        if t.distribution == ONED:
            ax = config.data_axis
            body = _compile_chain(meta, in_names, jit_names)

            def sharded(tree, counts):
                out, cnt = body(tree, counts[0])
                return out, cnt[None]
            fn = named_jit("fusedchain", C.smap(
                sharded, in_specs=(P(ax), P(ax)), out_specs=(P(ax), P(ax)),
                mesh=m))
        else:
            fn = named_jit(
                "fusedchain", _compile_chain(meta, in_names, jit_names),
                donate_argnums=(0,) if donate else ())
        lockstep.register_fusion_manifest(
            fp, _member_kinds(steps),
            1 if t.distribution == ONED and t.num_shards > 1 else 0)
        # static verification BEFORE first dispatch: collective
        # manifest + rank-invariance, donation audit, HBM estimate
        progcheck.check_jit(
            fn,
            (t.device_data(), t.counts_device())
            if t.distribution == ONED
            else (t.device_data(), jnp.asarray(t.nrows)),
            program=f"fused:{fp}", subsystem="fusion")

    # host-level fault point + composite-collective sequencing: the
    # fused program subsumes its members' dispatches, so the GROUP is
    # the unit chaos tests arm and peers must agree on
    if t.distribution == ONED and t.num_shards > 1:
        from bodo_tpu.runtime.resilience import maybe_inject
        maybe_inject("collective")
        lockstep.pre_fused(fp)

    from bodo_tpu.runtime import memory_governor as _mg
    t0 = _time.perf_counter()
    try:
        with _mg.preadmission_charge(f"fused:{fp}"):
            if t.distribution == ONED:
                out, cnts = fn(t.device_data(), t.counts_device())
                counts = np.asarray(tracing.host_read(
                    "fused_count", cnts)).reshape(-1).astype(np.int64)
            else:
                out, cnt = fn(t.device_data(), jnp.asarray(t.nrows))
                counts = None
                nrows = int(tracing.host_read("fused_count", cnt))
    except Exception as e:  # noqa: BLE001 - classified below
        _classify_dispatch_error(e, fp_sig, compiled)
        raise FusionFallback(str(e)) from e
    dt_s = _time.perf_counter() - t0
    if compiled:
        _programs[sig] = fn
        progcheck.mark_checked(_programs.handle_for(sig))
        _programs.record_compile("fused_stage", dt_s)
    if donate:
        _stats["donated"] += 1

    cols: Dict[str, Column] = {}
    jit_idx = {n: i for i, n in enumerate(jit_names)}
    for n in out_names:
        src = passthrough.get(n)
        if src is not None:
            cols[n] = t.columns[src]
            continue
        i = jit_idx[n]
        vr = E.expr_range(compose[n], t.columns)
        cols[n] = Column(out[2 * i], out[2 * i + 1], out_schema[n],
                         out_dicts.get(n), vr)
    if counts is not None:
        res = Table(cols, int(counts.sum()), ONED, counts)
    else:
        res = Table(cols, nrows, REP, None)
    res._fusion_compiled = compiled  # type: ignore[attr-defined]
    res._fusion_compile_s = dt_s if compiled else 0.0
    res._fusion_donated = donate  # type: ignore[attr-defined]
    return R.rebucket(res)


def _member_kinds(steps, agg=None) -> Tuple[str, ...]:
    out = tuple("filter" if isinstance(s, L.Filter) else "project"
                for s in steps)
    if agg is not None:
        out = out + ("aggregate",)
    return out


def _classify_dispatch_error(e: Exception, fp_sig, compiled: bool) -> None:
    """First-call errors mix trace/compile failures with genuine
    runtime faults (jit compiles lazily). OOM and degradable errors
    must reach the resilience envelope untouched; anything else on a
    fresh program is a build failure -> negative-cache and fall back."""
    from bodo_tpu.runtime import resilience
    from bodo_tpu.runtime.memory_governor import governor
    if resilience.is_degradable(e) or governor().is_oom(e):
        raise e
    if not compiled:
        # a previously-working program failing at dispatch is a runtime
        # fault, not a build problem — propagate for classification
        raise e
    _failed.add(fp_sig)


# ---------------------------------------------------------------------------
# fused terminal aggregate planning (host side)
# ---------------------------------------------------------------------------

def _plan_dense_agg(t: Table, agg: L.Aggregate, out_schema, out_dicts,
                    compose):
    """Derive dense-slot ranges for the fused aggregate's keys from the
    chain metadata: dictionary sizes for strings, 0/1 for bools, static
    expr_range over the input-composed key expression, and a device
    min/max reduce on the INPUT table for bare passthrough ints (a
    superset range is sound — empty slots compact away). Returns
    (sizes, los, n_slots, use_mxu) or None -> partial fusion."""
    from bodo_tpu import relational as R
    kn = list(agg.keys)
    ranges: List[Optional[Tuple[int, int]]] = []
    reduce_cols: List[Tuple[int, str]] = []
    for i, k in enumerate(kn):
        kdt = out_schema.get(k)
        ce = compose.get(k)
        if kdt is None or ce is None:
            return None
        if kdt is dt.STRING:
            dic = out_dicts.get(k)
            if dic is None:
                return None
            ranges.append((0, max(len(dic) - 1, 0)))
        elif kdt.kind == "b":
            ranges.append((0, 1))
        elif kdt.kind in ("i", "u") or kdt is dt.DATE:
            r = E.expr_range(ce, t.columns)
            if r is not None:
                ranges.append((int(r[0]), int(r[1])))
            elif isinstance(ce, E.ColRef):
                reduce_cols.append((i, ce.name))
                ranges.append(None)
            else:
                return None
        else:
            return None
    if reduce_cols:
        exact, _ = R._key_ranges(t, [nm for _, nm in reduce_cols],
                                 use_bounds=False)
        for (i, _), r in zip(reduce_cols, exact):
            if r is None:
                return None
            ranges[i] = (int(r[0]), int(r[1]))
    sizes = tuple(hi - lo + 1 for lo, hi in ranges)
    los = tuple(lo for lo, _ in ranges)
    n_slots = 1
    for s in sizes:
        n_slots *= int(s)
        if n_slots > config.dense_groupby_max_slots:
            return None
    if not (0 < n_slots <= config.dense_groupby_max_slots
            and n_slots <= 2 * max(t.nrows, 1)):
        return None
    from bodo_tpu.ops import pallas_kernels as PK
    specs = tuple(op for _, op, _ in agg.aggs)
    val_dtypes = []
    for c, _, _ in agg.aggs:
        vdt = out_schema.get(c)
        if vdt is None:
            return None
        val_dtypes.append(vdt.numpy)
    use_mxu = ((PK.use_pallas() or PK.FORCE_INTERPRET)
               and n_slots <= PK.MAX_MATMUL_SLOTS
               and R.dense_mxu_ok(t.capacity, val_dtypes, specs))
    return sizes, los, n_slots, use_mxu


def _run_fused_agg(t: Table, group: FusionGroup, donate: bool):
    """Fully-fused group with a terminal dense Aggregate over REP
    input: zero intermediate compactions — the chain's mask feeds
    relational.dense_agg_tail directly. Returns a Table, or None when
    the dense gate misses (caller partially fuses)."""
    from bodo_tpu import relational as R

    steps, agg = group.chain, group.agg
    fp_sig = ("fusedagg", _struct_sig(t), _steps_sig(steps),
              tuple(agg.keys), tuple(agg.aggs))
    if fp_sig in _failed:
        raise FusionFallback("negative-cached")
    try:
        meta, out_names, out_schema, out_dicts, compose = \
            _chain_meta(t, steps)
        plan = _plan_dense_agg(t, agg, out_schema, out_dicts, compose)
    except FusionFallback:
        raise
    except Exception as e:  # noqa: BLE001 - build failure -> unfused
        _failed.add(fp_sig)
        raise FusionFallback(str(e)) from e
    if plan is None:
        return None
    sizes, los, n_slots, use_mxu = plan
    kn = list(agg.keys)
    vn = [c for c, _, _ in agg.aggs]
    specs = tuple(op for _, op, _ in agg.aggs)
    donate = bool(donate) and jax.default_backend() in ("tpu", "gpu")
    route = R.dense_route(n_slots, specs, use_mxu)
    tracing.annotate(dense_route=route)
    sig = ("fusedagg", R._sig(t), _steps_sig(steps), tuple(kn),
           tuple(agg.aggs), sizes, los, route, donate)
    fp = _group_fp(fp_sig)
    fn = _programs.lookup(sig)
    compiled = fn is None
    if compiled:
        _budget_compile(sig)
        in_names = list(t.names)
        need = list(dict.fromkeys(kn + vn))

        @fusion_stage
        def fused(tree, count):
            cur, mask = _chain_body(meta, in_names, tree, count)
            atree = {n: cur[n] for n in need}
            return R.dense_agg_tail(atree, mask, kn, vn, specs, sizes,
                                    los, n_slots, use_mxu)

        fn = named_jit(
            "fusedagg", fused, donate_argnums=(0,) if donate else ())
        lockstep.register_fusion_manifest(
            fp, _member_kinds(steps, agg), 0)
        progcheck.check_jit(
            fn, (t.device_data(), jnp.asarray(t.nrows)),
            program=f"fused:{fp}", subsystem="fusion")
    from bodo_tpu.runtime import memory_governor as _mg
    t0 = _time.perf_counter()
    try:
        with groupby_route("fused", len(kn), t.nrows, n_slots,
                           dense_route=route), \
                _mg.preadmission_charge(f"fused:{fp}"):
            out_keys, out_vals, ng = fn(t.device_data(),
                                        jnp.asarray(t.nrows))
            nrows = int(tracing.host_read("fused_group_count", ng))
    except Exception as e:  # noqa: BLE001 - classified below
        from bodo_tpu.runtime import resilience
        from bodo_tpu.runtime.memory_governor import governor
        if resilience.is_degradable(e) or governor().is_oom(e):
            raise
        if not compiled or use_mxu:
            # a cached program failing at dispatch is a runtime fault;
            # a pallas kernel its gate admitted and the backend refused
            # is an error too, never a silent XLA re-run
            raise
        _failed.add(fp_sig)
        raise FusionFallback(str(e)) from e
    dt_s = _time.perf_counter() - t0
    if compiled:
        _programs[sig] = fn
        progcheck.mark_checked(_programs.handle_for(sig))
        _programs.record_compile("fused_stage", dt_s)
    if donate:
        _stats["donated"] += 1
    _stats["dense_" + route] += 1

    import types as _types
    cols: Dict[str, Column] = {}
    for kname, kd in zip(kn, out_keys):
        kdt = out_schema[kname]
        if kdt is dt.STRING:
            kd = kd.astype(np.int32)
        elif kdt.kind == "b":
            kd = kd.astype(bool)
        elif kd.dtype != kdt.numpy:
            kd = kd.astype(kdt.numpy)
        cols[kname] = Column(kd, None, kdt, out_dicts.get(kname))
    for (cname, op, oname), (vd, vv) in zip(agg.aggs, out_vals):
        src = _types.SimpleNamespace(dtype=out_schema[cname],
                                     dictionary=out_dicts.get(cname))
        cols[oname] = R._agg_out_col(src, op, vd, vv)
    res = R.shrink_to_fit(Table(cols, nrows, REP, None))
    res._fusion_compiled = compiled  # type: ignore[attr-defined]
    res._fusion_compile_s = dt_s if compiled else 0.0
    res._fusion_donated = donate  # type: ignore[attr-defined]
    res._fusion_pallas = use_mxu  # type: ignore[attr-defined]
    res._fusion_dense_route = route  # type: ignore[attr-defined]
    return res


# ---------------------------------------------------------------------------
# plan-group execution (called from physical._exec_inner)
# ---------------------------------------------------------------------------

def execute_group(group: FusionGroup, exec_child) -> Optional[Table]:
    """Execute one fusion group: run the input node normally, then
    dispatch the whole group as one compiled program. Returns the group
    ROOT's result table, or None to fall back to per-node execution.
    Runtime faults (OOM, degradable collectives, armed chaos faults)
    propagate — the stage-boundary envelope in physical.py owns them."""
    from bodo_tpu.plan import physical

    t = exec_child(group.input)
    force_rep = getattr(physical._degrade_tls, "force_rep", False)
    if force_rep and t.distribution == ONED:
        # degraded re-run: dispatch the REP program over a gathered
        # copy; the input node's cached 1D table stays untouched
        # (snapshot/restore is _try_degrade's job)
        t = t.gather()
    if config.plan_validate:
        from bodo_tpu.analysis.plan_validator import (
            PlanInvariantError, check_fusion_boundary)
        try:
            check_fusion_boundary(group.input, t.distribution,
                                  force_rep=force_rep)
        except PlanInvariantError:
            _stats["fallbacks"] += 1
            return None
    donate = group.donate_ok and not force_rep

    with tracing.event("fused_group", members=len(group.chain)
                       + (1 if group.agg else 0)) as ev:
        try:
            if group.agg is not None and t.distribution == REP:
                out = _run_fused_agg(t, group, donate)
                if out is not None:
                    _finish_group(group, t, out)
                    if ev is not None:
                        ev["rows"] = out.nrows
                    return out
                _stats["partial_agg"] += 1
            # chain-only group, or partial fusion: fuse the chain and
            # let relational.groupby_agg finish a 1D/over-budget agg
            chained = _run_chain(t, group.chain, donate=donate)
        except FusionFallback as e:
            _stats["fallbacks"] += 1
            log(2, f"fusion fallback ({len(group.chain)} stages): {e}")
            return None
        if group.agg is not None:
            from bodo_tpu import relational as R
            out = R.groupby_agg(chained, group.agg.keys, group.agg.aggs)
            out._fusion_compiled = getattr(
                chained, "_fusion_compiled", False)
            out._fusion_compile_s = getattr(
                chained, "_fusion_compile_s", 0.0)
            out._fusion_donated = getattr(
                chained, "_fusion_donated", False)
        else:
            out = chained
        _finish_group(group, t, out)
        if ev is not None:
            ev["rows"] = out.nrows
    return out


def _finish_group(group: FusionGroup, t: Table, out: Table) -> None:
    """Post-dispatch bookkeeping: donation invalidation, EXPLAIN
    annotations, stats."""
    from bodo_tpu.plan import physical
    _stats["groups_executed"] += 1
    donated = getattr(out, "_fusion_donated", False)
    if donated:
        # the program consumed the input buffers: drop both caches so
        # an OOM retry recomputes the input from ITS children instead
        # of touching dead memory. The ledger confirms XLA actually
        # freed the donated buffers (vs silently copying).
        xobs.verify_donation(t)
        group.input._cached = None
        physical._result_cache.pop(group.input.key(), None)
    xobs.track_table(out, "fused_stage")
    compiled = bool(getattr(out, "_fusion_compiled", False))
    info = {
        "members": group.member_ops(),
        "cache_hit": not compiled,
        "compile_s": round(float(getattr(out, "_fusion_compile_s", 0.0)),
                           6),
        "rows_in": int(t.nrows),
        "rows_out": int(out.nrows),
    }
    if getattr(out, "_fusion_pallas", False):
        info["pallas"] = True
    if hasattr(out, "_fusion_dense_route"):
        info["dense_route"] = out._fusion_dense_route
    group.root._fusion_info = info
    if tracing.is_tracing():
        from bodo_tpu.plan import explain
        root_path = getattr(group.root, "_explain_path", None)
        for m in group.members:
            if m is group.root:
                continue
            # rows=0: interior results never materialize — that is the
            # point of the fusion
            explain.record(m, rows=0, wall_s=0.0,
                           fusion={"fused_into": root_path or "?"})
            # instant event so `tracing.profile()` still counts every
            # absorbed operator kind; the wall time lives on the root
            with tracing.event(type(m).__name__, fused=1):
                pass


# ---------------------------------------------------------------------------
# streaming per-batch fused chains
# ---------------------------------------------------------------------------

def fused_batches(steps, src, sharded: bool = False):
    """Map a batch iterator through the fused chain, one compiled
    program per batch signature (batches share it after the first).
    On the first build failure the WHOLE stream falls back to per-node
    stages; runtime faults propagate as usual."""
    _stats["stream_chains"] += 1

    def _unfused(b: Table) -> Table:
        from bodo_tpu import relational as R
        from bodo_tpu.plan.physical import apply_projection
        for s in steps:
            if isinstance(s, L.Filter):
                b = R.filter_table(b, s.predicate)
            else:
                b = apply_projection(b, s.exprs)
        return b

    def gen():
        fused_ok = True
        for b in src:
            if getattr(b, "_device_decoded", False):
                # scan batch arrived straight off the device decode
                # path: ingest -> fused chain with no host round-trip
                _stats["device_scan_batches"] += 1
            if fused_ok:
                try:
                    yield _run_chain(b, steps)
                    continue
                except FusionFallback:
                    fused_ok = False
                    _stats["fallbacks"] += 1
            yield _unfused(b)

    return gen()
