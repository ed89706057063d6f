"""Fused join groups: the probe side of a hash join — and, over 1D
input with a terminal decomposable aggregate, the partial-agg bucket
shuffle — compiled INTO the whole-stage fusion program.

plan/fusion.py fuses [Filter|Projection]+ chains (+ an optional dense
aggregate) but stops at every Join and every shuffle: those dispatch
per-operator, each with its own host count sync, and the hot
profiles show they are the remaining two-thirds of the flat tax on the
taxi/TPC-H pipelines. This module extends group formation across both
boundaries:

  group shape       [below-chain -> Join(probe side) -> above-chain ->
                    optional Aggregate], claimed by
                    `try_join_group` (called by
                    `fusion.plan_fusion_groups` BEFORE the plain chain
                    grouper so the above-join chain isn't claimed away).
                    The build (right) child executes normally — it is
                    an input, not a member.

  device-resident   the build side's encoded key codes + slot-owner LUT
  build tables      (ops/hashtable.py scatter-claim table) are built
                    ONCE per distinct build-key buffer identity and
                    kept on device in a process-wide LRU
                    (`build_hash_table`); repeat probes — streaming
                    batches against one build, a build subplan shared
                    by several joins, repeated queries — skip the
                    build entirely. A build side with more rows than
                    its keys' sources have values
                    (`relational.keys_must_repeat` on the planner's
                    `stats.key_ndv_bound`) is not built at all: the
                    group falls back as it would after finding the
                    duplicates. The per-node hash join
                    (relational._join_hash_try) draws from the SAME
                    cache, and every cached LUT is tracked in the
                    device-buffer ledger (xla_observatory) under op
                    ``join_build_lut``.

  fused probe body  the below-chain runs lazily (fusion._chain_body),
                    probe keys encode with the SAME aligned layout as
                    the build (`encode_columns_aligned` with an
                    all-True null-column layout, so build entries are
                    probe-independent), `probe_slots` walks the
                    double-hash sequence, build columns gather by the
                    hit index, and the above-chain continues over the
                    JOINED tree with the hit mask ANDed in (inner) —
                    ONE compaction for the whole region, or zero when
                    a left join has no filters.

  in-program        a decomposable Aggregate over a 1D probe traces the
  shuffle           whole two-phase groupby INSIDE the shard_map body:
                    per-shard partial agg (ops/groupby.groupby_local)
                    -> fixed-capacity bucket shuffle
                    (parallel/shuffle.shuffle_partials, whose
                    `lax.all_to_all` now lives inside the compiled
                    program, with the Pallas one-hot MXU bucket
                    histogram when the kernel gate is open) -> combine
                    + finalize. The aggregate need NOT be terminal:
                    [Filter|Projection] members ABOVE it (the `post`
                    chain) trace over the finalized groups inside the
                    same program, so the shuffle sits mid-program. The
                    overflow flag collapses into the group's single
                    host count sync; the host grows the bucket capacity
                    and recompiles on overflow (×4 up to the
                    always-safe bound).

  1D build sides    a genuinely big 1D build (broadcast decision says
                    no host gather) no longer falls back: each shard
                    `lax.all_gather`s the build key/emit columns inside
                    the program and builds the claim table as
                    replicated compute; the dup-keys/claim-exhausted
                    flag folds into the group's one sync, and the
                    manifest declares the in-program ``all_gather``.

  lockstep / comm   the group manifest declares its in-program
                    collectives (`register_fusion_manifest(...,
                    in_program=("all_to_all",))`); a multi-shard
                    dispatch is sequence-numbered as ONE composite
                    collective via `lockstep.pre_fused`, and the comm
                    observatory attributes an ``all_to_all`` accounting
                    row at site ``fused[<fp>]`` from the manifest
                    (`comm.record_in_program`) since the in-program
                    collective never passes a host dispatch hook.

Failure policy matches plan/fusion.py: build/trace problems raise
FusionFallback (per-node re-execution, negative-cached by structural
signature); runtime faults — OOM, degradable collectives, armed chaos
faults — propagate so the stage-boundary envelope degrades the group
to a replicated re-run (the REP chain program + host aggregate).
Donation is deliberately NOT used in fused join programs: an
unresolved-probe fallback after a donating dispatch would leave the
input node's cache pointing at freed buffers. Build-side reuse is the
device-resident cache, proven by the ledger + hit counters, not by
probe donation.

Disable with `BODO_TPU_FUSION_JOIN=0` / `set_config(fusion_join=False)`
(plain chain fusion keeps working); the build cache is bounded by
`JOIN_BUILD_CACHE_SIZE` entries.
"""

from __future__ import annotations

import time as _time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bodo_tpu.analysis import lockstep
from bodo_tpu.analysis import progcheck
from bodo_tpu.config import config
from bodo_tpu.ops import hashtable as HT
from bodo_tpu.ops import kernels as K
from bodo_tpu.ops import pallas_kernels as PK
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan import expr as E
from bodo_tpu.plan import fusion as F
from bodo_tpu.plan import logical as L
from bodo_tpu.plan import stats as plan_stats
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import (Column, ONED, REP, Table,
                                  round_capacity)
from bodo_tpu.runtime import xla_observatory as xobs
from bodo_tpu.utils.logging import log

# NOTE: same import rule as plan/fusion.py — relational, physical and
# parallel/shuffle import the fusion layer at module level, so they may
# only be imported INSIDE functions here.

_stats = {"groups_planned": 0, "groups_executed": 0, "partial": 0,
          "fallbacks": 0, "agg_inprogram": 0, "shuffle_retries": 0,
          "post_chain_fused": 0, "build_gather_inprogram": 0}

# device-resident build cache accounting (process-wide)
_cstats = {"hits": 0, "misses": 0, "builds": 0, "negative": 0,
           "negative_hits": 0, "evictions": 0}


def stats() -> dict:
    out = dict(_stats)
    out["build_cache"] = build_cache_stats()
    return out


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0
    for k in _cstats:
        _cstats[k] = 0


# ---------------------------------------------------------------------------
# group formation
# ---------------------------------------------------------------------------

class JoinGroup:
    """One fusable [chain -> Join -> chain -> agg? -> chain?] region.

    below    [Filter|Projection] members UNDER the join's probe (left)
             child, bottom-up (below[0] consumes the input node)
    join     the L.Join member (how in inner/left, hash-probe eligible)
    above    [Filter|Projection] members over the joined schema,
             bottom-up
    agg      optional Aggregate (group root unless a post chain sits
             over it)
    post     [Filter|Projection] members OVER the aggregate's output
             schema, bottom-up — the non-terminal-shuffle extension:
             the in-program bucket shuffle is no longer forced to sit
             at the group root; the post chain traces over the
             finalized groups inside the SAME program
    input    plan node feeding the below chain (executed normally)
    build    the join's right child (executed normally — its table is
             the build side, cached device-resident, NOT a member)

    API-compatible with fusion.FusionGroup where the shared machinery
    needs it (`members`, `member_ops`, `root`, `input`, `donate_ok`) so
    `fusion._finish_group` handles both.
    """

    __slots__ = ("below", "join", "above", "agg", "post", "root",
                 "input", "build", "donate_ok")

    def __init__(self, below, join, above, agg, input_node, post=()):
        self.below = list(below)
        self.join = join
        self.above = list(above)
        self.agg = agg
        self.post = list(post)
        if self.post:
            self.root = self.post[-1]
        elif agg is not None:
            self.root = agg
        else:
            self.root = self.above[-1] if self.above else join
        self.input = input_node
        self.build = join.right
        # fused join programs never donate: an unresolved-probe fallback
        # after donation would leave input._cached on freed buffers
        self.donate_ok = False

    @property
    def members(self):
        """Members root-first (display order)."""
        out = list(reversed(self.post))
        if self.agg is not None:
            out.append(self.agg)
        out.extend(reversed(self.above))
        out.append(self.join)
        out.extend(reversed(self.below))
        return out

    def member_ops(self) -> Tuple[str, ...]:
        return tuple(type(m).__name__ for m in self.members)


def _post_agg_claimable(aggnode: L.Node, parents) -> bool:
    """Plan-time gate for claiming chain members ABOVE an aggregate
    (the non-terminal-shuffle shape): only worth it when the aggregate
    can decompose into the in-program shuffle — runtime still checks
    the probe distribution; when this returns False the plain chain
    grouper keeps the post chain and the terminal-agg shape applies."""
    from bodo_tpu.ops.groupby import DECOMPOSE
    if aggnode._cached is not None or not F._agg_fusable(aggnode):
        return False
    if parents.get(id(aggnode), 0) != 1:
        return False
    return all(op in DECOMPOSE for _, op, _ in aggnode.aggs)


def try_join_group(node: L.Node, parents, claimed) -> Optional[JoinGroup]:
    """Claim a [below-chain -> Join -> above-chain -> agg? ->
    post-chain?] region rooted at `node`, or None when no join-crossing
    group forms here (the caller then tries the plain chain grouper).
    Same interior rules as fusion._try_group: members must be
    single-parent and unmaterialized."""
    if not (config.fusion and config.fusion_join):
        return None
    agg = None
    post_td: List[L.Node] = []  # top-down while walking
    top = node
    if isinstance(node, L.Aggregate):
        if not F._agg_fusable(node) or node._cached is not None:
            return None
        agg = node
        top = node.child
        if parents.get(id(top), 0) != 1 or top._cached is not None:
            return None
    above_td: List[L.Node] = []  # top-down while walking
    cur = top
    while isinstance(cur, (L.Filter, L.Projection)) and \
            cur._cached is None and F._node_fusable(cur):
        if cur is not node and parents.get(id(cur), 0) != 1:
            break
        above_td.append(cur)
        cur = cur.child
    if agg is None and above_td and isinstance(cur, L.Aggregate) and \
            _post_agg_claimable(cur, parents):
        # the walked members sit ABOVE a decomposable aggregate: they
        # become the POST chain (traced over the finalized groups, after
        # the in-program shuffle) and the above-join chain walk restarts
        # under the aggregate
        agg = cur
        post_td = above_td
        above_td = []
        cur = cur.child
        if parents.get(id(cur), 0) != 1 or cur._cached is not None:
            return None
        while isinstance(cur, (L.Filter, L.Projection)) and \
                cur._cached is None and F._node_fusable(cur):
            if parents.get(id(cur), 0) != 1:
                break
            above_td.append(cur)
            cur = cur.child
    if not isinstance(cur, L.Join):
        return None
    join = cur
    if join.how not in ("inner", "left") or not join.left_on or \
            join._cached is not None:
        return None
    if join is not node and parents.get(id(join), 0) != 1:
        return None
    # plan-time key dtype identity: the fused body requires structurally
    # identical encodes on both sides (the per-node path casts/unifies;
    # fusing a cast-needing join would silently change key equality)
    try:
        ls, rs = join.left.schema, join.right.schema
        for lk, rk in zip(join.left_on, join.right_on):
            if ls[lk] is not rs[rk]:
                return None
    except Exception:  # noqa: BLE001 - unknown schema -> not fusable
        return None
    below_td: List[L.Node] = []
    cur = join.left
    while isinstance(cur, (L.Filter, L.Projection)) and \
            cur._cached is None and F._node_fusable(cur):
        if parents.get(id(cur), 0) != 1:
            break
        below_td.append(cur)
        cur = cur.child
    if agg is None and not above_td and not below_td:
        return None  # a lone join fuses nothing
    g = JoinGroup(list(reversed(below_td)), join,
                  list(reversed(above_td)), agg, cur,
                  list(reversed(post_td)))
    if any(id(m) in claimed for m in g.members):
        return None  # defensive: overlapping walk already claimed one
    _stats["groups_planned"] += 1
    return g


def _suffix_maps(lnames, rnames, left_on, right_on, suffixes):
    """relational._suffix_columns on bare name lists (the fused planner
    works over schemas, not Tables): returns (lmap, rmap); right-side
    key columns merged into an equally-named left key are dropped."""
    overlap = (set(lnames) & set(rnames)) - (set(left_on) & set(right_on))
    lmap = {n: (n + suffixes[0] if n in overlap else n) for n in lnames}
    rmap = {n: (n + suffixes[1] if n in overlap else n) for n in rnames
            if not (n in right_on and left_on[right_on.index(n)] == n)}
    return lmap, rmap


# ---------------------------------------------------------------------------
# device-resident build-side hash tables
# ---------------------------------------------------------------------------

# Device-resident build-side hash tables kept per process (LRU): each
# entry pins the build table's encoded key codes + slot-owner LUT on
# device so repeat probes (streaming batches, reused build subplans)
# skip the build entirely.
JOIN_BUILD_CACHE_SIZE = 32

# build-key buffer identity -> {"codes", "owner", "refs", "hits"} entry,
# or None (negative verdict: duplicate build keys / unresolved claim).
# Entries hold strong refs to the source key buffers so id() identity
# stays meaningful for the entry's lifetime.
_build_cache: "OrderedDict[tuple, Optional[dict]]" = OrderedDict()

# build-program cache keyed ("joinbuild", key dtypes, T, layout):
# registered with the program observatory like every other kernel cache
from bodo_tpu.utils.kernel_cache import (  # noqa: E402
    KERNEL_CACHE_SIZE, KernelCache, named_jit)
_build_jit_cache = KernelCache(maxsize=KERNEL_CACHE_SIZE,
                               subsystem="fusion_join")


def _build_key(right: Table, right_on, null_cols, null_equal) -> tuple:
    cols = [right.column(k) for k in right_on]
    return (tuple(id(c.data) for c in cols),
            tuple(c.dtype.name for c in cols),
            tuple(c.valid is not None for c in cols),
            bool(null_equal), tuple(null_cols),
            int(right.nrows), int(right.capacity))


def _cache_put(key, ent) -> None:
    _build_cache[key] = ent
    _build_cache.move_to_end(key)
    while len(_build_cache) > JOIN_BUILD_CACHE_SIZE:
        _build_cache.popitem(last=False)
        _cstats["evictions"] += 1


def build_hash_table(right: Table, right_on, null_cols,
                     null_equal: bool) -> Optional[Tuple]:
    """Device-resident build: (codes, owner) for `right`'s key columns
    over a claim table of size `HT.table_size(right.capacity)`,
    LRU-cached by key-buffer identity so repeat probes against the same
    build table skip the build (and its host dup-check sync) entirely.
    Returns None when the build side has duplicate keys or the claim
    rounds exhausted (cached negatively — the caller's sort join owns
    that case). One host sync per MISS, zero per hit."""
    key = _build_key(right, right_on, null_cols, null_equal)
    if key in _build_cache:
        ent = _build_cache[key]
        _build_cache.move_to_end(key)
        if ent is None:
            _cstats["negative_hits"] += 1
            return None
        ent["hits"] += 1
        _cstats["hits"] += 1
        return ent["codes"], ent["owner"]
    _cstats["misses"] += 1
    nk = len(right_on)
    T = HT.table_size(right.capacity)
    kcols = [right.column(k) for k in right_on]
    sig = ("joinbuild",
           tuple((c.dtype.name, c.valid is not None) for c in kcols),
           nk, bool(null_equal), T, tuple(null_cols))
    fn = _build_jit_cache.get(sig)
    built_fresh = fn is None
    if built_fresh:
        ncols = tuple(null_cols)

        def bbody(arrays, count):
            cap = arrays[0][0].shape[0]
            codes, null_ok = HT.encode_columns_aligned(arrays, ncols,
                                                       null_equal)
            ok = K.row_mask(count, cap)
            if null_ok is not None:
                ok = ok & null_ok
            slot, owner, _r, unresolved = HT.claim_slots(codes, ok, T)
            cnt = jnp.zeros(T, jnp.int32).at[
                jnp.where(slot >= 0, slot, T)].add(1, mode="drop")
            dup = jnp.any(cnt > 1)
            return codes, owner, dup | unresolved

        fn = named_jit("join_build_fused", bbody)
        _build_jit_cache[sig] = fn
    karrays = tuple((c.data, c.valid) for c in kcols)
    if built_fresh:
        # the cached slot-owner LUT outlives this dispatch: donation of
        # any build input would leave the cache pointing at freed
        # buffers, so "never donate" is a checked contract here
        h = _build_jit_cache.handle_for(sig)
        progcheck.check_jit(fn, (karrays, jnp.asarray(right.nrows)),
                            program="joinbuild",
                            subsystem="fusion_join",
                            forbid_donation=True, obs_handle=h)
        progcheck.mark_checked(h)
    bcodes, owner, bad = fn(karrays, jnp.asarray(right.nrows))
    _cstats["builds"] += 1
    # the one budgeted sync per build MISS (dup-key verdict)
    if bool(jax.device_get(bad)):  # dispatch-boundary
        _cstats["negative"] += 1
        _cache_put(key, None)
        return None
    # the slot-owner LUT is the device-resident artifact probes reuse:
    # ledger-track it so the HBM observatory (and the donation verifier's
    # reuse proof in tests) can see the one buffer shared across probes
    xobs.track_buffer(owner, "join_build_lut")
    _cache_put(key, {"codes": bcodes, "owner": owner, "hits": 0,
                     "refs": tuple(c.data for c in kcols)})
    return bcodes, owner


def prime_build(right: Table, right_on, null_equal: bool = True) -> bool:
    """Opportunistically warm the build cache (streaming executors call
    this when a join's build side finalizes, so the first probe batch
    already hits). Uses the probe-independent all-True null layout —
    the same layout every probe path keys with. Best-effort: never
    raises; returns True when an entry (positive or negative) exists."""
    if not (config.fusion_join and config.hash_join):
        return False
    try:
        if right.distribution != REP or right.nrows == 0 or not right_on:
            return False
        null_cols = (True,) * len(right_on)
        build_hash_table(right, list(right_on), null_cols, null_equal)
        return True
    except Exception:  # noqa: BLE001 - priming must never break a query
        return False


def build_cache_stats() -> dict:
    out = dict(_cstats)
    out["size"] = len(_build_cache)
    out["entry_hits"] = {i: e["hits"] for i, (_k, e) in
                        enumerate(_build_cache.items())
                        if e is not None}
    return out


def cached_build_entry(right: Table, right_on, null_cols=None,
                       null_equal: bool = True) -> Optional[dict]:
    """Introspection for tests/doctor: the live cache entry for this
    build table (None when absent or negative)."""
    if null_cols is None:
        null_cols = (True,) * len(right_on)
    return _build_cache.get(
        _build_key(right, list(right_on), tuple(null_cols), null_equal))


def clear_build_cache() -> None:
    _build_cache.clear()
    _build_jit_cache.clear()


# ---------------------------------------------------------------------------
# fused probe body
# ---------------------------------------------------------------------------

def _make_probe_body(below_meta, in_names, left_on, null_cols,
                     null_equal, T, how, lmap, below_names, build_emit,
                     rmap, above_meta):
    """Traced region [below-chain -> encode -> probe -> gather ->
    above-chain]: returns (joined tree, live mask, probe-unresolved
    flag). Shared by the chain-exit and fused-aggregate program
    variants."""

    @F.fusion_stage
    def body(ptree, pcount, bvals, bcodes, owner):
        cur, mask = F._chain_body(below_meta, in_names, ptree, pcount)
        keys = [cur[k] for k in left_on]
        codes, null_ok = HT.encode_columns_aligned(keys, null_cols,
                                                   null_equal)
        live = mask if null_ok is None else (mask & null_ok)
        idx, p_unres = HT.probe_slots(bcodes, owner, codes, live, T)
        hit = idx >= 0
        safe = jnp.maximum(idx, 0)
        joined = {lmap[n]: cur[n] for n in below_names}
        for n in build_emit:
            d, v = bvals[n]
            od = d[safe]
            ov = hit if v is None else (hit & v[safe])
            joined[rmap[n]] = (od, ov)
        if how == "inner":
            mask = mask & hit
        # left join: unmatched probe rows stay live with all build
        # columns invalid (ov already False where hit is False)
        cur2, mask2 = F._chain_body_masked(above_meta, joined, mask)
        return cur2, mask2, p_unres

    return body


def _flatten_tree(cur, names):
    flat = []
    for n in names:
        d, v = cur[n]
        flat.append(d)
        flat.append(v)
    return tuple(flat)


def _make_build_gather(right_on, need, null_cols, null_equal, T, S,
                       cap_shard, ax):
    """In-program build over a 1D build side: each shard all_gathers the
    build's key/emit columns (rank-order concat; padding resolved by
    the gathered per-shard counts) and builds the claim table as
    replicated compute — the collective lives INSIDE the compiled
    program, replacing the host gather the per-node broadcast join
    would do. Returns (gathered tree, codes, owner LUT, bad flag);
    `bad` (duplicate keys / claim rounds exhausted) folds into the
    group's single host sync."""

    @F.fusion_stage
    def gather_build(btree, bcounts):
        allc = C.all_gather_rows(bcounts, ax)            # [S]
        row = jnp.arange(S * cap_shard)
        ok = (row % cap_shard) < allc[row // cap_shard]
        gathered = {}
        for n in need:
            d, v = btree[n]
            gd = C.all_gather_rows(d, ax)
            gv = None if v is None else C.all_gather_rows(v, ax)
            gathered[n] = (gd, gv)
        keys = [gathered[k] for k in right_on]
        codes, null_ok = HT.encode_columns_aligned(keys, null_cols,
                                                   null_equal)
        bok = ok if null_ok is None else (ok & null_ok)
        slot, owner, _r, unresolved = HT.claim_slots(codes, bok, T)
        cnt = jnp.zeros(T, jnp.int32).at[
            jnp.where(slot >= 0, slot, T)].add(1, mode="drop")
        bad = jnp.any(cnt > 1) | unresolved
        return gathered, codes, owner, bad

    return gather_build


# ---------------------------------------------------------------------------
# group execution (called from physical._exec_inner)
# ---------------------------------------------------------------------------

def execute_join_group(group: JoinGroup, exec_child) -> Optional[Table]:
    """Execute one fused join group: run the input and build nodes
    normally, then dispatch the whole probe region as one compiled
    program. Returns the group ROOT's result, or None to fall back to
    per-node execution. Runtime faults propagate to the resilience
    envelope (a degraded re-run gathers the probe and re-dispatches the
    REP program, finishing any aggregate host-side)."""
    from bodo_tpu.plan import physical
    from bodo_tpu.utils import tracing

    t = exec_child(group.input)
    b = exec_child(group.build)
    force_rep = getattr(physical._degrade_tls, "force_rep", False)
    if force_rep:
        if t.distribution == ONED:
            t = t.gather()
        if b.distribution == ONED:
            b = b.gather()
    if config.plan_validate:
        from bodo_tpu.analysis.plan_validator import (
            PlanInvariantError, check_fusion_boundary)
        try:
            check_fusion_boundary(group.input, t.distribution,
                                  force_rep=force_rep)
        except PlanInvariantError:
            _stats["fallbacks"] += 1
            return None

    with tracing.event("fused_join_group",
                       members=len(group.members)) as ev:
        try:
            out = _run_join_group(t, b, group)
        except F.FusionFallback as e:
            _stats["fallbacks"] += 1
            log(2, f"fused-join fallback "
                   f"({len(group.members)} members): {e}")
            return None
        _stats["groups_executed"] += 1
        F._finish_group(group, t, out)
        info = group.root._fusion_info
        if info is not None:
            # surface the collectives the program subsumed in EXPLAIN
            # ANALYZE next to the absorbed plan members, matching what
            # the manifest declares for this group
            coll = []
            if getattr(out, "_fusion_build_gather", False):
                coll.append("all_gather")
            if getattr(out, "_fusion_join_inprogram", False):
                info["members"] = tuple(info["members"]) + ("Shuffle",)
                coll.append("all_to_all")
            if coll:
                info["in_program_collectives"] = tuple(coll)
        if ev is not None:
            ev["rows"] = out.nrows
    return out


def _plan_fused_agg(t: Table, agg: L.Aggregate, out_schema, out_dicts):
    """Gate + static plan for tracing the two-phase aggregate (partial
    -> in-program bucket shuffle -> combine -> finalize) inside the
    probe program. Returns a plan dict, or None -> partial fusion (the
    chain+join program runs, relational.groupby_agg finishes)."""
    if t.distribution != ONED:
        return None  # REP aggregate has no shuffle to absorb
    from bodo_tpu.ops.groupby import DECOMPOSE
    from bodo_tpu.parallel.shuffle import _plan_decomposition
    kn = list(agg.keys)
    specs = tuple(op for _, op, _ in agg.aggs)
    vn = [c for c, _, _ in agg.aggs]
    if not kn or any(op not in DECOMPOSE for op in specs):
        return None
    for n in kn + vn:
        d = out_schema.get(n)
        if d is None or dt.is_decimal(d):
            return None
        if d is dt.STRING and n not in out_dicts and n in vn:
            return None
    for c in vn:
        if out_schema[c] is dt.STRING:
            return None  # string value aggs finalize host-side
    try:
        partial_specs, combine_specs, layout = _plan_decomposition(specs)
    except NotImplementedError:
        return None
    value_dtypes = tuple(str(np.dtype(out_schema[c].numpy)) for c in vn)
    return {"kn": kn, "vn": vn, "specs": specs,
            "partial_specs": partial_specs,
            "combine_specs": combine_specs, "layout": layout,
            "value_dtypes": value_dtypes}


def _run_join_group(t: Table, b: Table, group: JoinGroup) -> Table:
    """Build (cached) + compile (cached) + dispatch the fused join
    program; raises FusionFallback on build/trace failure."""
    from bodo_tpu import relational as R

    if not t.names or not b.names:
        raise F.FusionFallback("empty schema")
    if not config.hash_join:
        raise F.FusionFallback("hash join disabled")
    build_inprogram = False
    if b.distribution == ONED:
        # same runtime broadcast decision as the per-node path: a small
        # sharded build side replicates (one gather) so the probe never
        # shuffles; a genuinely big 1D build over a 1D probe gathers
        # INSIDE the program (lax.all_gather in the shard_map body) and
        # builds the claim table as replicated compute
        from bodo_tpu.plan import adaptive
        if adaptive.join_broadcast_decision(b, t):
            with F.exchange("broadcast", rows=b.nrows):
                b = b.gather()
        elif t.distribution == ONED and t.num_shards > 1:
            build_inprogram = True
    if b.distribution != REP and not build_inprogram:
        raise F.FusionFallback("1D build side")
    if b.nrows == 0:
        raise F.FusionFallback("empty build side")
    join = group.join
    left_on, right_on = list(join.left_on), list(join.right_on)
    nk = len(left_on)
    how, null_equal, suffixes = join.how, join.null_equal, join.suffixes
    agg = group.agg
    if not build_inprogram and R.keys_must_repeat(b, right_on, [
            plan_stats.key_ndv_bound(join.right, k) for k in right_on]):
        # more rows than the keys' sources have values: the build would
        # run, sync and find a key twice. No reduction a query: the
        # planner keeps a resident column's span on the column (PR 32)
        F.join_build_skipped()
        raise F.FusionFallback("duplicate build keys")

    fp_sig = ("fusedjoin", F._struct_sig(t), F._struct_sig(b),
              F._steps_sig(group.below), F._steps_sig(group.above),
              tuple(left_on), tuple(right_on), how, null_equal,
              t.distribution,
              (tuple(agg.keys), tuple(agg.aggs)) if agg else None,
              F._steps_sig(group.post), build_inprogram)
    if fp_sig in F._failed:
        raise F.FusionFallback("negative-cached")

    try:
        (below_meta, below_names, below_schema, below_dicts,
         _below_compose) = F._chain_meta(t, group.below)
    except Exception as e:  # noqa: BLE001 - build failure -> unfused
        F._failed.add(fp_sig)
        raise F.FusionFallback(str(e)) from e

    # runtime key compatibility: the plan-time gate checked schema
    # dtypes, but dictionary unification / dtype promotion happen at
    # runtime in the per-node path — the fused body does neither
    for lk, rk in zip(left_on, right_on):
        ldt = below_schema.get(lk)
        bc = b.columns.get(rk)
        if ldt is None or bc is None:
            raise F.FusionFallback("join key missing from chain output")
        if ldt is not bc.dtype:
            raise F.FusionFallback("join key dtype mismatch")
        if ldt is dt.STRING and below_dicts.get(lk) is not bc.dictionary:
            # dict-encoded keys compare by code: only sound when both
            # sides share ONE dictionary object (per-node unifies)
            raise F.FusionFallback("join key dictionaries differ")

    # probe-independent null layout: a null code column is always legal
    # (zeros when a side can't produce nulls), and keying the build
    # cache on it makes entries reusable across every probe shape
    null_cols = (True,) * nk

    lmap, rmap = _suffix_maps(below_names, list(b.names), left_on,
                              right_on, suffixes)
    build_emit = [n for n in b.names if n in rmap]
    joined_schema = {lmap[n]: below_schema[n] for n in below_names}
    joined_dicts = {lmap[n]: below_dicts[n] for n in below_names
                    if n in below_dicts}
    for n in build_emit:
        c = b.columns[n]
        joined_schema[rmap[n]] = c.dtype
        if c.dictionary is not None:
            joined_dicts[rmap[n]] = c.dictionary
    try:
        (above_meta, out_names, out_schema, out_dicts,
         _above_compose) = F._chain_meta_from(joined_schema,
                                              joined_dicts, group.above)
    except Exception as e:  # noqa: BLE001 - build failure -> unfused
        F._failed.add(fp_sig)
        raise F.FusionFallback(str(e)) from e

    T = HT.table_size(b.capacity)
    if build_inprogram:
        bcodes = owner = None  # built inside the program
    else:
        built = build_hash_table(b, right_on, null_cols, null_equal)
        if built is None:
            raise F.FusionFallback("duplicate build keys")
        bcodes, owner = built

    agg_plan = None
    if agg is not None:
        agg_plan = _plan_fused_agg(t, agg, out_schema, out_dicts)
        if agg_plan is not None:
            missing = [n for n in agg_plan["kn"] + agg_plan["vn"]
                       if n not in out_names]
            if missing:
                agg_plan = None

    post_meta = post_names = post_schema = post_dicts = None
    if group.post:
        if agg_plan is None:
            # the post chain was claimed on the promise of the
            # in-program aggregate; without it (REP probe, gate miss)
            # the per-node path owns the region. Data-dependent — no
            # negative cache.
            raise F.FusionFallback(
                "post-agg chain without in-program aggregate")
        agg_schema = dict(agg.schema)
        agg_dicts = {k: out_dicts[k] for k in agg_plan["kn"]
                     if k in out_dicts}
        try:
            (post_meta, post_names, post_schema, post_dicts,
             _post_compose) = F._chain_meta_from(agg_schema, agg_dicts,
                                                 group.post)
        except Exception as e:  # noqa: BLE001 - build failure -> unfused
            F._failed.add(fp_sig)
            raise F.FusionFallback(str(e)) from e

    in_names = list(t.names)
    body = _make_probe_body(below_meta, in_names, left_on, null_cols,
                            null_equal, T, how, lmap, below_names,
                            build_emit, rmap, above_meta)
    fp = F._group_fp(fp_sig)
    multi = t.distribution == ONED and t.num_shards > 1

    if build_inprogram:
        bneed = list(dict.fromkeys(right_on + build_emit))
        gb = _make_build_gather(right_on, bneed, null_cols, null_equal,
                                T, t.num_shards, b.shard_capacity,
                                config.data_axis)
        probe_body = body

        @F.fusion_stage
        def body(ptree, pcount, btree, bcounts):
            bvals_g, bcodes_g, owner_g, bbad = gb(btree, bcounts)
            cur2, mask2, p_unres = probe_body(ptree, pcount, bvals_g,
                                              bcodes_g, owner_g)
            return cur2, mask2, p_unres | bbad

        bargs = (b.select(bneed).device_data(), b.counts_device())
        bspecs = (P(config.data_axis), P(config.data_axis))
    else:
        bargs = (b.select(build_emit).device_data(), bcodes, owner)
        bspecs = (P(), P(), P())

    with F.join_route("fused", nk, t.nrows, b.nrows):
        if agg_plan is not None:
            out = _dispatch_agg(t, b, group, body, bargs, bspecs, agg_plan,
                                out_schema, out_dicts, post_meta,
                                post_names, post_schema, post_dicts, fp,
                                fp_sig, multi, build_inprogram)
        else:
            out = _dispatch_chain(t, b, group, body, bargs, bspecs,
                                  out_names, out_schema, out_dicts, fp,
                                  fp_sig, multi, build_inprogram)
    if agg_plan is None and agg is not None:
        # partial fusion: the chain+probe fused, the aggregate (REP
        # input, non-decomposable op, or gate miss) finishes per-op
        _stats["partial"] += 1
        chained, out = out, R.groupby_agg(out, agg.keys, agg.aggs)
        for attr in ("_fusion_compiled", "_fusion_compile_s",
                     "_fusion_donated"):
            setattr(out, attr, getattr(chained, attr, False))
    if build_inprogram:
        _stats["build_gather_inprogram"] += 1
        F.exchange_inprogram(b.nrows)
    return out


def _register_manifest(group: JoinGroup, fp: str, multi: bool,
                       inprogram: bool, gather: bool = False) -> None:
    ops = (F._member_kinds(group.below) + ("join",)
           + F._member_kinds(group.above,
                             group.agg if inprogram else None))
    if inprogram:
        ops = ops + ("shuffle",) + F._member_kinds(group.post)
    coll = (("all_gather",) if gather else ()) + \
        (("all_to_all",) if inprogram else ())
    lockstep.register_fusion_manifest(fp, ops, 1 if multi else 0,
                                      in_program=coll)


def _pre_dispatch(fp: str, multi: bool) -> float:
    """Host-level fault point + composite-collective sequencing (the
    fused program subsumes its members' dispatches — the GROUP is the
    unit chaos tests arm and lockstep peers must agree on)."""
    if not multi:
        return 0.0
    from bodo_tpu.runtime.resilience import maybe_inject
    maybe_inject("collective")
    return lockstep.pre_fused(fp)


def _dispatch_chain(t, b, group, body, bargs, bspecs, out_names,
                    out_schema, out_dicts, fp, fp_sig, multi,
                    build_inprogram) -> Table:
    """Chain-exit variant: fused program returns the joined/filtered
    columns (one compaction, or zero for a filter-less left join)."""
    from bodo_tpu import relational as R
    from bodo_tpu.parallel.shuffle import _mesh_key

    m = mesh_mod.get_mesh()
    has_filter = any(isinstance(s, L.Filter)
                     for s in group.below + group.above)
    compact_needed = has_filter or group.join.how == "inner"
    rorder = list(group.join.right_on) + \
        [n for n in b.names if n not in group.join.right_on]
    sig = ("fusedjoin", _mesh_key(m), R._sig(t),
           R._sig(b.select(rorder)), F._steps_sig(group.below),
           F._steps_sig(group.above), tuple(group.join.left_on),
           tuple(group.join.right_on), group.join.how,
           group.join.null_equal, t.distribution, compact_needed,
           build_inprogram)
    fn = F._programs.lookup(sig)
    compiled = fn is None
    if compiled:
        F._budget_compile(sig)

        def fused(ptree, pcount, bargs_):
            cur2, mask2, p_unres = body(ptree, pcount, *bargs_)
            flat = _flatten_tree(cur2, out_names)
            if compact_needed:
                out, cnt = K.compact(mask2, flat)
            else:
                out, cnt = flat, pcount
            return out, cnt, p_unres

        if t.distribution == ONED:
            ax = config.data_axis

            def sharded(ptree, pcounts, bargs_):
                out, cnt, unres = fused(ptree, pcounts[0], bargs_)
                return out, cnt[None], unres[None]
            fn = named_jit("fusedjoin", C.smap(
                sharded, in_specs=(P(ax), P(ax), bspecs),
                out_specs=(P(ax), P(ax), P(ax)), mesh=m))
        else:
            fn = named_jit("fusedjoin", fused)
        _register_manifest(group, fp, multi, inprogram=False,
                           gather=build_inprogram)
        if t.distribution == ONED:
            _ck_args = (t.device_data(), t.counts_device(), bargs)
        else:
            _ck_args = (t.device_data(), jnp.asarray(t.nrows), bargs)
        progcheck.check_jit(
            fn, _ck_args, program=f"fused:{fp}", subsystem="fusion_join",
            declared_collectives=(("all_gather",) if build_inprogram
                                  else None) if multi else None)

    from bodo_tpu.runtime import memory_governor as _mg
    w = _pre_dispatch(fp, multi)
    t0 = _time.perf_counter()
    try:
        with _mg.preadmission_charge(f"fused:{fp}"):
            if t.distribution == ONED:
                out, cnts, unres = fn(t.device_data(),
                                      t.counts_device(), bargs)
                cnts_h, unres_h = jax.device_get((cnts, unres))  # dispatch-boundary
                counts = np.asarray(cnts_h).reshape(-1).astype(np.int64)
                bad = bool(np.asarray(unres_h).any())
            else:
                out, cnt, unres = fn(t.device_data(),
                                     jnp.asarray(t.nrows), bargs)
                cnt_h, unres_h = jax.device_get((cnt, unres))  # dispatch-boundary
                counts = None
                nrows = int(cnt_h)
                bad = bool(unres_h)
    except Exception as e:  # noqa: BLE001 - classified below
        F._classify_dispatch_error(e, fp_sig, compiled)
        raise F.FusionFallback(str(e)) from e
    dt_s = _time.perf_counter() - t0
    if compiled:
        F._programs[sig] = fn
        F._programs.record_compile("fused_join", dt_s)
        progcheck.mark_checked(F._programs.handle_for(sig))
    if multi and build_inprogram:
        from bodo_tpu.parallel import comm
        comm.record_in_program(fp, bytes_in=comm.table_bytes(b),
                               wall_s=dt_s, wait_s=w)
    if bad:
        # data-dependent: probe-round exhaustion (sort join owns it) or
        # a bad in-program build (duplicate keys / claim exhaustion) —
        # no negative cache, a different batch may resolve fine
        raise F.FusionFallback("probe unresolved or bad build")

    cols: Dict[str, Column] = {}
    for i, n in enumerate(out_names):
        cols[n] = Column(out[2 * i], out[2 * i + 1], out_schema[n],
                         out_dicts.get(n))
    if counts is not None:
        res = Table(cols, int(counts.sum()), ONED, counts)
    else:
        res = Table(cols, nrows, REP, None)
    res._fusion_compiled = compiled  # type: ignore[attr-defined]
    res._fusion_compile_s = dt_s if compiled else 0.0
    res._fusion_donated = False  # type: ignore[attr-defined]
    res._fusion_build_gather = build_inprogram  # type: ignore[attr-defined]
    return R.rebucket(res)


def _dispatch_agg(t, b, group, body, bargs, bspecs, agg_plan,
                  out_schema, out_dicts, post_meta, post_names,
                  post_schema, post_dicts, fp, fp_sig, multi,
                  build_inprogram) -> Table:
    """Fully-fused variant over a 1D probe: the two-phase aggregate —
    partial agg, fixed-capacity bucket shuffle (`lax.all_to_all` INSIDE
    the shard_map body), combine, finalize — traces into the same
    program as the chain+probe, and a non-empty POST chain (the
    non-terminal-shuffle shape) continues over the finalized groups
    inside that program too. One host sync carries (group counts,
    shuffle overflow, probe unresolved); on overflow the host grows the
    bucket capacity ×4 (to the always-safe bound) and recompiles."""
    from bodo_tpu import relational as R
    from bodo_tpu.ops.groupby import (DECOMPOSE, agg_dtype,
                                      groupby_local)
    from bodo_tpu.parallel.shuffle import (_finalize, _mesh_key,
                                           shuffle_partials)
    import types as _types

    agg = group.agg
    kn, vn = agg_plan["kn"], agg_plan["vn"]
    specs = agg_plan["specs"]
    partial_specs = agg_plan["partial_specs"]
    combine_specs = agg_plan["combine_specs"]
    layout = agg_plan["layout"]
    value_dtypes = agg_plan["value_dtypes"]
    nkk = len(kn)
    need = list(dict.fromkeys(kn + vn))

    m = mesh_mod.get_mesh()
    ax = config.data_axis
    S = m.shape[ax]
    cap_shard = max(t.shard_capacity, 1)
    safe_cap = round_capacity(cap_shard)
    bucket_cap = min(round_capacity(
        int(config.shuffle_skew_factor * cap_shard / max(S, 1)) + 64),
        safe_cap)
    rorder = list(group.join.right_on) + \
        [n for n in b.names if n not in group.join.right_on]
    base_sig = ("fusedjoinagg", _mesh_key(m), R._sig(t),
                R._sig(b.select(rorder)), F._steps_sig(group.below),
                F._steps_sig(group.above), tuple(group.join.left_on),
                tuple(group.join.right_on), group.join.how,
                group.join.null_equal, tuple(kn), tuple(agg.aggs),
                F._steps_sig(group.post), build_inprogram)

    while True:
        final_cap = S * bucket_cap
        sig = base_sig + (bucket_cap, final_cap)
        fn = F._programs.lookup(sig)
        compiled = fn is None
        if compiled:
            F._budget_compile(sig)
            bc_static, fc_static = bucket_cap, final_cap

            @F.fusion_stage
            def sharded(ptree, pcounts, bargs_):
                cur2, mask2, p_unres = body(ptree, pcounts[0], *bargs_)
                flat = _flatten_tree(cur2, need)
                packed, cnt = K.compact(mask2, flat)
                pairs = {n: (packed[2 * i], packed[2 * i + 1])
                         for i, n in enumerate(need)}
                keys = tuple(pairs[n] for n in kn)
                values = [pairs[c] for c in vn]
                p_inputs = keys + tuple(
                    values[i] for i, op in enumerate(specs)
                    for _ in DECOMPOSE[op])
                cap = mask2.shape[0]
                pk, pv, ng = groupby_local(p_inputs, cnt, partial_specs,
                                           cap, nkk)
                rk, rv, cnt2, ovf = shuffle_partials(
                    pk, pv, nkk, S, bc_static, ng, ax)
                fk, fv, ng2 = groupby_local(rk + rv, cnt2,
                                            combine_specs, fc_static,
                                            nkk)
                finals = []
                for i, op in enumerate(specs):
                    off, nparts = layout[i]
                    finals.append(_finalize(
                        op, fv[off:off + nparts],
                        jnp.dtype(value_dtypes[i])))
                if post_meta is None:
                    return ((fk, tuple(finals)), ng2[None], ovf[None],
                            p_unres[None])
                # non-terminal shuffle: cast the finalized groups to
                # their logical dtypes (same rules as the host exit
                # path / relational._agg_out_col — no decimals, the
                # plan gate rejects them) and run the post chain over
                # them, all still inside the program
                tree = {}
                for kname, (kd, kv) in zip(kn, fk):
                    kdt = out_schema[kname]
                    if kdt is dt.STRING:
                        kd = kd.astype(jnp.int32)
                    elif kdt.kind == "b":
                        kd = kd.astype(bool)
                    elif kd.dtype != kdt.numpy:
                        kd = kd.astype(kdt.numpy)
                    tree[kname] = (kd, kv)
                for (cname, op, oname), (vd, vv) in zip(agg.aggs,
                                                        finals):
                    rdt = agg_dtype(op, out_schema[cname])
                    if vd.dtype != rdt.numpy:
                        vd = vd.astype(rdt.numpy)
                    tree[oname] = (vd, vv)
                gmask = K.row_mask(ng2, fc_static)
                cur3, mask3 = F._chain_body_masked(post_meta, tree,
                                                   gmask)
                outp, ng3 = K.compact(mask3,
                                      _flatten_tree(cur3, post_names))
                return (outp, ng3[None], ovf[None], p_unres[None])

            fn = named_jit("fusedjoin_groupby", C.smap(
                sharded, in_specs=(P(ax), P(ax), bspecs),
                out_specs=(P(ax), P(ax), P(ax), P(ax)), mesh=m))
            _register_manifest(group, fp, multi, inprogram=True,
                               gather=build_inprogram)
            progcheck.check_jit(
                fn, (t.device_data(), t.counts_device(), bargs),
                program=f"fused:{fp}", subsystem="fusion_join",
                declared_collectives=((("all_gather",)
                                       if build_inprogram else ())
                                      + ("all_to_all",))
                if multi else None)

        from bodo_tpu.runtime import memory_governor as _mg
        w = _pre_dispatch(fp, multi)
        t0 = _time.perf_counter()
        try:
            with _mg.preadmission_charge(f"fused:{fp}"):
                res_out, ngs, ovf, unres = fn(
                    t.device_data(), t.counts_device(), bargs)
                ngs_h, ovf_h, unres_h = jax.device_get(  # dispatch-boundary
                    (ngs, ovf, unres))
        except Exception as e:  # noqa: BLE001 - classified below
            F._classify_dispatch_error(e, fp_sig, compiled)
            raise F.FusionFallback(str(e)) from e
        dt_s = _time.perf_counter() - t0
        if compiled:
            F._programs[sig] = fn
            F._programs.record_compile("fused_join", dt_s)
            progcheck.mark_checked(F._programs.handle_for(sig))
        if multi:
            from bodo_tpu.parallel import comm
            comm.record_in_program(fp, bytes_in=comm.table_bytes(t),
                                   wall_s=dt_s, wait_s=w)
        if bool(np.asarray(unres_h).any()):
            raise F.FusionFallback("probe unresolved or bad build")
        if bool(np.asarray(ovf_h).any()):
            if bucket_cap >= safe_cap:
                raise F.FusionFallback(
                    "shuffle overflow at safe capacity")
            bucket_cap = min(bucket_cap * 4, safe_cap)
            _stats["shuffle_retries"] += 1
            continue
        break

    _stats["agg_inprogram"] += 1
    counts = np.asarray(ngs_h).reshape(-1).astype(np.int64)
    cols: Dict[str, Column] = {}
    if post_meta is not None:
        _stats["post_chain_fused"] += 1
        for i, n in enumerate(post_names):
            cols[n] = Column(res_out[2 * i], res_out[2 * i + 1],
                             post_schema[n], post_dicts.get(n))
    else:
        fk, finals = res_out
        for kname, (kd, kv) in zip(kn, fk):
            kdt = out_schema[kname]
            if kdt is dt.STRING:
                kd = kd.astype(np.int32)
            elif kdt.kind == "b":
                kd = kd.astype(bool)
            elif kd.dtype != kdt.numpy:
                kd = kd.astype(kdt.numpy)
            cols[kname] = Column(kd, kv, kdt, out_dicts.get(kname))
        for (cname, op, oname), (vd, vv) in zip(agg.aggs, finals):
            src = _types.SimpleNamespace(dtype=out_schema[cname],
                                         dictionary=out_dicts.get(cname))
            cols[oname] = R._agg_out_col(src, op, vd, vv)
    res = R.shrink_to_fit(Table(cols, int(counts.sum()), ONED, counts))
    res._fusion_compiled = compiled  # type: ignore[attr-defined]
    res._fusion_compile_s = dt_s if compiled else 0.0
    res._fusion_donated = False  # type: ignore[attr-defined]
    res._fusion_join_inprogram = True  # type: ignore[attr-defined]
    res._fusion_build_gather = build_inprogram  # type: ignore[attr-defined]
    # the in-program shuffle's bucket histogram routes through the
    # Pallas one-hot MXU accumulate when the kernel gate is open
    if (PK.use_pallas() or PK.FORCE_INTERPRET) and \
            (S + 1) <= PK.MAX_MATMUL_SLOTS:
        res._fusion_pallas = True  # type: ignore[attr-defined]
    return res
