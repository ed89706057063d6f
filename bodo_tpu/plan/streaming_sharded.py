"""Distributed (1D) streaming execution: sharded batches + overlapped
all_to_all shuffle.

TPU-native redesign of the reference's distributed streaming operators
(reference: bodo/libs/streaming/_shuffle.h:777 `IncrementalShuffleState`
async sends overlapping compute, streaming/_groupby.cpp
`GroupbyState::UpdateGroupsAndCombine`, streaming/_join.h:892). Where the
reference posts MPI_Ialltoallv per batch and polls completion, here every
batch runs ONE fused jitted shard_map step —

    local partial aggregation
      → hash-bucket fixed-capacity `lax.all_to_all` to the owner shard
      → merge into the per-shard packed state

— and the host never syncs inside the loop: group counts stay on device,
state capacities are sized from host-known row-count BOUNDS, and the
shuffle-overflow flag is checked one batch LATE (deferred sync). By the
time batch k+1 is decoded on host, batch k's device work has already been
dispatched — XLA's async dispatch gives the same compute/communication
overlap the reference gets from MPI_Ialltoallv. On overflow the step is
re-run from a kept pre-state at a larger bucket capacity (the analogue of
the reference's partition re-splitting, streaming/_join.h:267); the
always-safe bound is the per-shard batch capacity, so the retry loop
terminates.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from bodo_tpu import relational as R
from bodo_tpu.config import config
from bodo_tpu.ops.groupby import (DECOMPOSE, groupby_local, groupby_merge,
                                  result_dtype)
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.parallel.shuffle import (_MESHES, _mesh_key,
                                       _plan_decomposition, _finalize,
                                       shuffle_partials)
from bodo_tpu.plan.streaming import _bucket_cap as _pow2_cap
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import Column, ONED, REP, Table
from bodo_tpu.utils import tracing
from bodo_tpu.utils.kernel_cache import cached_builder, named_jit
from bodo_tpu.utils.logging import log


# ---------------------------------------------------------------------------
# sharded re-capacity / slicing (shard_map helpers)
# ---------------------------------------------------------------------------

@cached_builder("streaming")
def _build_recap(mesh_key, old_per: int, new_per: int):
    mesh = _MESHES[mesh_key]
    axis = config.data_axis

    def body(tree):
        def one(a):
            if a is None:
                return None
            if new_per <= old_per:
                return a[:new_per]
            pad = jnp.zeros((new_per - old_per,) + a.shape[1:], a.dtype)
            return jnp.concatenate([a, pad])
        return {n: (one(d), one(v)) for n, (d, v) in tree.items()}

    return named_jit("stream_recapacity", C.smap(
        body, in_specs=(P(axis),), out_specs=P(axis), mesh=mesh))


def shard_recapacity(t: Table, new_per: int, mesh=None) -> Table:
    """Change a 1D table's PER-SHARD capacity (device-side pad/slice, no
    host transit). Rows stay packed at the front of each shard."""
    assert t.distribution == ONED
    m = mesh or mesh_mod.get_mesh()
    per = t.shard_capacity
    if per == new_per:
        return t
    assert new_per >= int(t.counts.max(initial=0)), (new_per, t.counts)
    fn = _build_recap(_mesh_key(m), per, new_per)
    tree = fn(t.device_data())
    return t.with_device_data(tree, nrows=t.nrows, counts=t.counts)


@cached_builder("streaming")
def _build_slicer(mesh_key, per: int, bcap: int):
    mesh = _MESHES[mesh_key]
    axis = config.data_axis

    def body(tree, off):
        o = off[0]

        def one(a):
            if a is None:
                return None
            return lax.dynamic_slice_in_dim(a, o, bcap)
        return {n: (one(d), one(v)) for n, (d, v) in tree.items()}

    return named_jit("stream_slice", C.smap(
        body, in_specs=(P(axis), P(axis)), out_specs=P(axis), mesh=mesh))


def table_batches_sharded(t: Table, batch_rows: int,
                          mesh=None) -> Iterator[Table]:
    """Slice a 1D table into fixed-capacity 1D batches. All shards step in
    lockstep (a shard that ran out of rows contributes count-0 batches) so
    every per-batch collective sees the full mesh."""
    assert t.distribution == ONED
    m = mesh or mesh_mod.get_mesh()
    S = mesh_mod.num_shards(m)
    bcap = _pow2_cap(batch_rows)
    per = t.shard_capacity
    if per % bcap != 0:
        per = ((per + bcap - 1) // bcap) * bcap
        t = shard_recapacity(t, per, m)
    fn = _build_slicer(_mesh_key(m), per, bcap)
    max_count = int(t.counts.max(initial=0))
    n_batches = max(1, -(-max_count // bcap))
    off_shard = mesh_mod.row_sharding(m)
    for b in range(n_batches):
        off = b * bcap
        counts_b = np.clip(t.counts - off, 0, bcap).astype(np.int64)
        off_dev = jax.device_put(
            np.full((S,), off, dtype=np.int32), off_shard)
        tree = fn(t.device_data(), off_dev)
        yield t.with_device_data(tree, nrows=int(counts_b.sum()),
                                 counts=counts_b)


def parquet_batches_sharded(path: str, columns: Optional[Sequence[str]],
                            batch_rows: int, mesh=None) -> Iterator[Table]:
    """Stream a parquet dataset as 1D batches: fixed row windows scatter
    over the mesh at a FIXED per-shard capacity so every downstream
    kernel compiles once. With device decode on, the inner source ships
    raw page bytes and decodes on-chip (io/device_decode.py), so the
    host never materializes decoded windows at all."""
    from bodo_tpu.plan.streaming import parquet_batches
    from bodo_tpu.runtime.io_pool import prefetched
    # prefetch below the scatter: Arrow decode of window k+1 overlaps
    # the device-side shard/recapacity of window k
    return _shard_batches(
        prefetched(parquet_batches(path, columns, batch_rows),
                   label="parquet_sharded"),
        batch_rows, mesh)


def csv_batches_sharded(path: str, columns: Optional[Sequence[str]],
                        parse_dates, batch_rows: int,
                        mesh=None) -> Iterator[Table]:
    """Stream a CSV file as 1D batches (byte-range chunked host parse →
    fixed-capacity scatter; reference: the parallel chunked CSV scan,
    bodo/io/_csv_json_reader.cpp)."""
    from bodo_tpu.plan.streaming import csv_batches
    from bodo_tpu.runtime.io_pool import prefetched
    return _shard_batches(
        prefetched(csv_batches(path, columns, parse_dates, batch_rows),
                   label="csv_sharded"),
        batch_rows, mesh)


def _shard_batches(src: Iterator[Table], batch_rows: int,
                   mesh=None) -> Iterator[Table]:
    m = mesh or mesh_mod.get_mesh()
    S = mesh_mod.num_shards(m)
    bcap_s = _pow2_cap(-(-batch_rows // S))
    with mesh_mod.use_mesh(m):
        for rep_batch in src:
            sh = rep_batch.shard()
            out = shard_recapacity(sh, bcap_s, m)
            # scan provenance survives the scatter: fusion's
            # device_scan_batches counter reads this flag off sharded
            # batches too
            if getattr(rep_batch, "_device_decoded", False):
                out._device_decoded = True
            yield out


# ---------------------------------------------------------------------------
# sharded streaming groupby
# ---------------------------------------------------------------------------

@cached_builder("streaming")
def _build_sharded_step(mesh_key, num_keys: int, specs: Tuple[str, ...],
                        bucket_cap: int, state_cap: int):
    """One streamed-groupby step: partial-agg the batch, shuffle partial
    rows to their hash-owner shard, merge into the per-shard state. The
    whole step is one jitted shard_map program — XLA overlaps the
    all_to_all with the surrounding compute, and nothing in it forces a
    host sync."""
    mesh = _MESHES[mesh_key]
    axis = config.data_axis
    S = mesh.shape[axis]
    partial_specs, combine_specs, _ = _plan_decomposition(specs)

    def body(batch_arrays, batch_counts, state_arrays, state_counts):
        count = batch_counts[0]
        n_state = state_counts[0]
        cap = batch_arrays[0][0].shape[0]
        keys = batch_arrays[:num_keys]
        values = batch_arrays[num_keys:]
        p_inputs = tuple(keys) + tuple(
            values[i] for i, op in enumerate(specs)
            for _ in DECOMPOSE[op])
        pk, pv, ng = groupby_local(p_inputs, count, partial_specs, cap,
                                   num_keys)
        rk, rv, cnt, ovf = shuffle_partials(pk, pv, num_keys, S,
                                            bucket_cap, ng, axis)
        state_flat = tuple(state_arrays[0]) + tuple(state_arrays[1])
        mk, mv, ng2 = groupby_merge(state_flat, rk + rv,
                                    n_state, cnt, combine_specs,
                                    state_cap, num_keys)
        return (mk, mv), ng2[None], ovf[None]

    shd = C.smap(body, in_specs=(P(axis), P(axis), P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P(axis)), mesh=mesh)
    return named_jit("groupby_stream_sharded", shd)


class ShardedGroupbyAccumulator:
    """Distributed streaming groupby over 1D batches.

    Per-shard packed state holds the groups HASH-OWNED by that shard
    (keys + partial-agg columns); finish() finalizes in place, so the
    result is already a valid 1D table — no gather anywhere.

    Pipelining: push(k) dispatches step k FIRST; overflow flags and
    output group counts resolve in WINDOWS of RESOLVE_WINDOW dispatches
    — all of a window's flags plus the newest resolved count travel in
    ONE batched `tracing.host_read`, so host syncs per stage are
    O(batches / W), not O(batches), and by the time a window retires its
    flags are long computed (no read ever stalls the pipe). The host
    therefore knows the exact per-shard group count with an at-most-W-
    batch lag and sizes the state capacity as known_count + one recv
    window per in-flight dispatch — flat in the number of batches. The
    rare overflow rewinds to the kept pre-state of the FIRST overflowed
    dispatch and replays from there at a larger bucket capacity
    (O(W batches + 1 state) extra memory, the price of never blocking
    on a flag read).
    """

    RESOLVE_WINDOW = 8

    def __init__(self, keys: Sequence[str], aggs: Sequence[Tuple],
                 mesh=None):
        self.keys = list(keys)
        self.aggs = list(aggs)
        self.specs = tuple(op for _, op, _ in aggs)
        self.partial_specs, self.combine_specs, self.layout = \
            _plan_decomposition(self.specs)
        self.mesh = mesh or mesh_mod.get_mesh()
        self.S = mesh_mod.num_shards(self.mesh)
        self._mk = _mesh_key(self.mesh)
        self._state: Optional[Tuple] = None   # ((mk, mv), counts_dev)
        self._state_meta: Optional[List[Tuple]] = None
        self._known = 0          # exact max per-shard groups, 1 batch stale
        self._bucket_cap: Optional[int] = None
        self._state_cap = 0
        # unresolved dispatches: (pre_state, inputs, ovf, out, bcap)
        self._queue: List[Tuple] = []
        self._template: Optional[Table] = None
        self.peak_state_cap = 0  # observability: max per-shard state rows
        self.n_retries = 0       # observability: overflow replays
        from bodo_tpu.runtime.memory_governor import governor
        self._grant = governor().admit("stream_groupby")

    # -- schema plumbing ----------------------------------------------------

    def _plan_meta(self, batch: Table) -> None:
        """(name, DType, dictionary, src_column) for state columns: keys
        then one column per partial spec. src_column tracks which batch
        column's dictionary a dict-coded state column follows."""
        meta = []
        for k in self.keys:
            c = batch.column(k)
            meta.append((k, c.dtype, c.dictionary,
                         k if c.dictionary is not None else None))
        pi = 0
        for (cname, op, _), parts in zip(self.aggs,
                                         (DECOMPOSE[s] for s in self.specs)):
            src = batch.column(cname)
            for pop in parts:
                if pop in ("min", "max", "first", "last"):
                    meta.append((f"__p{pi}", src.dtype, src.dictionary,
                                 cname if src.dictionary is not None
                                 else None))
                else:
                    meta.append((f"__p{pi}",
                                 dt.from_numpy(result_dtype(pop,
                                                            src.dtype.numpy)),
                                 None, None))
                pi += 1
        self._state_meta = meta

    def _zero_state(self, state_cap: int) -> Tuple:
        nk = len(self.keys)
        sh = mesh_mod.row_sharding(self.mesh)
        cols = []
        for name, dtype, _, _ in self._state_meta:
            d = jax.device_put(
                np.zeros((self.S * state_cap,), dtype=dtype.numpy), sh)
            v = jax.device_put(np.zeros((self.S * state_cap,), bool), sh)
            cols.append((d, v))
        counts = jax.device_put(np.zeros((self.S,), np.int64), sh)
        return ((tuple(cols[:nk]), tuple(cols[nk:])), counts)

    def _batch_inputs(self, batch: Table):
        arrays = tuple((batch.column(k).data, batch.column(k).valid)
                       for k in self.keys)
        arrays += tuple((batch.column(c).data, batch.column(c).valid)
                        for c, _, _ in self.aggs)
        return arrays, batch.counts_device()

    # -- streaming protocol -------------------------------------------------

    def push(self, batch: Table) -> None:
        assert batch.distribution == ONED
        if self._template is None:
            self._template = batch
            self._plan_meta(batch)
        if batch.nrows == 0 and self._state is not None:
            return
        bcap = batch.shard_capacity
        if self._bucket_cap is None:
            tight = int(config.shuffle_skew_factor * bcap / self.S) + 64
            self._bucket_cap = min(_pow2_cap(tight), _pow2_cap(bcap))

        # re-code sharded state onto any grown dictionaries
        bdicts = self._batch_dicts(batch)
        self._absorb_dicts(bdicts)

        # state sizing gates on the last EXACT count plus this batch's
        # worst case only — NOT a worst-case sum over the in-flight
        # queue, which would force a drain (host sync) every few batches
        # whenever the state is small relative to the batch size. Queued
        # dispatches may have grown the true count past _known; that is
        # caught at window resolution (the step's ng2 is the TRUE group
        # count even when the state scatter dropped rows past capacity)
        # and repaired by the same rewind-replay that handles bucket
        # overflow, so the steady state keeps its O(B/W) sync cadence
        # and its flat capacity.
        recv = min(self.S * self._bucket_cap, self.S * bcap)
        need = self._known + recv
        if self._state is None:
            # first push: _known is definitionally stale (no resolve has
            # run yet) — budget one extra recv window of headroom so the
            # steady-state capacity is reached immediately rather than
            # via a growth step after the first exact count lands
            self._state_cap = _pow2_cap(max(2 * recv, 1))
            self._state = self._zero_state(self._state_cap)
        elif need > self._state_cap:
            self._state_cap = _pow2_cap(need)
            self._state = self._recap_state(self._state, self._state_cap)
        self._dispatch(self._batch_inputs(batch), bcap, bdicts)
        # resolve in windows, always after launching the newest dispatch:
        # a full window's flags retire with one batched host read, and
        # the newest dispatch stays in flight to keep decode(n+1)
        # overlapping compute(n)
        if len(self._queue) >= self.RESOLVE_WINDOW:
            self._resolve_window(len(self._queue) - 1)

    def _dispatch(self, inputs, bcap: int, bdicts) -> None:
        from bodo_tpu.parallel import comm
        arrays, counts = inputs
        pre_state = self._state
        step = _build_sharded_step(self._mk, len(self.keys), self.specs,
                                   self._bucket_cap, self._state_cap)
        (st, cnts) = pre_state
        # per-batch lockstep sequence number (ROADMAP item 6: streaming
        # collectives carry seq numbers like the host-level dispatchers).
        # Overflow replays re-enter here too, but the ovf flags are SPMD-
        # deterministic so every rank replays the same batches — the seq
        # streams stay aligned.
        wait = 0.0
        if self.S > 1:
            from bodo_tpu.analysis import lockstep
            wait = lockstep.pre_collective("stream1d_step")
        in_bytes = sum(int(getattr(leaf, "nbytes", 0))
                       for leaf in jax.tree_util.tree_leaves(arrays))
        with tracing.event("stream1d_step"), \
                comm.collective_span("stream1d_step", bytes_in=in_bytes,
                                     wait_s=wait) as sp:
            mkv, ng2, ovf = step(arrays, counts, st, cnts)
            sp["bytes_out"] = sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(mkv))
        self._state = (mkv, ng2)
        self._queue.append({
            "pre_state": pre_state,
            "pre_meta": list(self._state_meta),
            "inputs": inputs, "bdicts": bdicts,
            "ovf": ovf, "out_counts": ng2, "bcap": bcap,
            "scap": self._state_cap})
        self.peak_state_cap = max(self.peak_state_cap, self._state_cap)
        row_bytes = sum(m[1].numpy.itemsize + 1 for m in self._state_meta)
        self._grant.update(self.S * self._state_cap * row_bytes)

    def _resolve_oldest(self) -> None:
        self._resolve_window(1)

    def _resolve_window(self, k: int) -> None:
        """Retire the oldest k dispatches with ONE batched host read:
        every flag in the window plus the newest retired dispatch's
        group counts ride a single `tracing.host_read`."""
        if not self._queue or k <= 0:
            return
        k = min(k, len(self._queue))
        entries = self._queue[:k]
        got = tracing.host_read(  # dispatch-boundary
            "stream.resolve", [e["ovf"] for e in entries]
            + [e["out_counts"] for e in entries])
        flags = [np.asarray(f).reshape(-1) for f in got[:k]]
        counts = [int(np.asarray(c).reshape(-1).max(initial=0))
                  for c in got[k:]]
        # two overflow modes per entry: the shuffle bucket dropped rows
        # (ovf flag), or the state scatter dropped groups — visible as
        # the TRUE group count ng2 exceeding the capacity the step was
        # built with (push sizes state from a one-window-stale count)
        first_bad = next(
            (i for i, (f, e2, c) in enumerate(zip(flags, entries, counts))
             if f.any() or c > e2["scap"]), None)
        if first_bad is None:
            self._queue = self._queue[k:]
            self._known = counts[-1]
            return
        bucket_bad = bool(flags[first_bad].any())
        # dispatches before the first overflow resolved clean — adopt
        # the last clean count
        self._queue = self._queue[first_bad:]
        if first_bad > 0:
            self._known = counts[first_bad - 1]
        e = self._queue.pop(0)
        # overflow: every dispatch from this one on was built on a state
        # missing the dropped rows — rewind state AND dictionary metadata
        # to just before it, then replay them all at a larger bucket
        # capacity (terminates: per-shard batch capacity is always safe).
        # Each replayed batch re-applies its own dictionary growth so the
        # rewound (older-dict) state is re-coded exactly as it was the
        # first time through.
        self.n_retries += 1
        replay = [e] + self._queue
        self._queue = []
        self._state = e["pre_state"]
        self._state_meta = list(e["pre_meta"])
        self._state_cap = e["scap"]  # capacity the rewound state has
        safe = max(_pow2_cap(x["bcap"]) for x in replay)
        if bucket_bad:
            self._bucket_cap = min(self._bucket_cap * 4, safe)
        # state overflow needs no explicit growth here: _known is exact
        # after the rewind, so the replay loop's known+recv sizing grows
        # the state just enough before re-dispatching
        log(1, f"stream1d overflow ({'bucket' if bucket_bad else 'state'})"
               f": replaying {len(replay)} batches at "
               f"bucket_cap={self._bucket_cap}")
        for x in replay:
            self._absorb_dicts(x["bdicts"])
            while True:
                recv = min(self.S * self._bucket_cap, self.S * x["bcap"])
                need = self._known + recv
                if need > self._state_cap:
                    self._state_cap = _pow2_cap(need)
                    self._state = self._recap_state(self._state,
                                                    self._state_cap)
                self._dispatch(x["inputs"], x["bcap"], x["bdicts"])
                e2 = self._queue.pop()
                f2, c2 = (np.asarray(a).reshape(-1) for a in
                          tracing.host_read(  # dispatch-boundary
                              "stream.resolve",
                              [e2["ovf"], e2["out_counts"]]))
                if not f2.any():
                    self._known = int(c2.max(initial=0))
                    break
                self._state = e2["pre_state"]
                assert self._bucket_cap < safe, \
                    "shuffle overflow at safe capacity"
                self._bucket_cap = min(self._bucket_cap * 4, safe)

    def _recap_state(self, state, new_cap: int):
        (mk, mv), cnts = state
        nk = len(self.keys)
        tree = {}
        for i, (d, v) in enumerate(tuple(mk) + tuple(mv)):
            tree[f"c{i:03d}"] = (d, v)
        fn = _build_recap(self._mk, next(iter(tree.values()))[0].shape[0]
                          // self.S, new_cap)
        out = fn(tree)
        cols = [out[f"c{i:03d}"] for i in range(nk + len(mv))]
        return ((tuple(cols[:nk]), tuple(cols[nk:])), cnts)

    def _batch_dicts(self, batch: Table) -> List[Optional[np.ndarray]]:
        """The batch's dictionary per state column (None for non-dict)."""
        return [batch.column(src).dictionary if src is not None else None
                for (_, _, _, src) in self._state_meta]

    def _absorb_dicts(self, bdicts: List[Optional[np.ndarray]]) -> None:
        """Re-code dict-coded state columns when the source dictionary has
        grown (elementwise LUT gather — sharding-preserving, no
        collective). Invariant (held by the sources' DictTracker and by
        batches sliced from one table): a batch's dictionary is always a
        superset of every earlier batch's, so the state dict is a subset
        of the incoming one and batch codes never need re-coding here."""
        if self._state is None:
            return
        from bodo_tpu.plan.streaming import remap_codes
        nk = len(self.keys)
        (mk, mv), cnts = self._state
        cols = list(mk) + list(mv)
        changed = False
        for i, (name, dtype, sdict, src) in enumerate(self._state_meta):
            if src is None:
                continue
            bdict = bdicts[i]
            if sdict is None or bdict is None or sdict is bdict or \
                    len(bdict) == len(sdict):
                continue
            d, v = cols[i]
            col = remap_codes(Column(d, v, dtype, sdict), bdict)
            cols[i] = (col.data, col.valid)
            self._state_meta[i] = (name, dtype, bdict, src)
            changed = True
        if changed:
            self._state = ((tuple(cols[:nk]), tuple(cols[nk:])), cnts)

    def finish(self) -> Table:
        assert self._template is not None, "empty stream"
        while self._queue:
            self._resolve_window(len(self._queue))
        nk = len(self.keys)
        (mk, mv), cnts_dev = self._state
        counts = np.asarray(tracing.host_read(  # dispatch-boundary
            "stream.group_count", cnts_dev)).reshape(-1).astype(np.int64)
        cols: Dict[str, Column] = {}
        for (name, dtype, dic, _), (d, v) in zip(self._state_meta[:nk],
                                                 mk):
            cols[name] = Column(d, v, dtype, dic)
        # finalize partials → final agg columns (elementwise on the
        # sharded arrays; sharding-preserving)
        pcols = list(mv)
        for i, (cname, op, oname) in enumerate(self.aggs):
            off, n = self.layout[i]
            src_dt = self._template.column(cname).dtype
            d, v = _finalize(op, tuple(pcols[off + j] for j in range(n)),
                             jnp.dtype(src_dt.numpy))
            if op in ("min", "max", "first", "last"):
                rdt, dic = src_dt, self._state_meta[nk + off][2]
            else:
                rdt = dt.from_numpy(result_dtype(op, src_dt.numpy))
                dic = None
            cols[oname] = Column(d, v, rdt, dic)
        self._grant.release()
        return Table(cols, int(counts.sum()), ONED, counts)


# ---------------------------------------------------------------------------
# sharded stream compilation (mirrors streaming._build_stream)
# ---------------------------------------------------------------------------

class ShardedStreamJoin:
    """Per-batch 1D probe against a replicated build side (the runtime
    broadcast join over a stream; reference: streaming hash join with a
    broadcast build, bodo/libs/streaming/_join.h:892)."""

    def __init__(self, build: Table, left_on, right_on, how, suffixes,
                 null_equal: bool = True):
        self.left_on, self.right_on = left_on, right_on
        self.how, self.suffixes = how, suffixes
        self.null_equal = null_equal
        self.build = build.gather() if build.distribution != REP else build
        # warm the device-resident build table at construction: every
        # probe batch then hits the LRU entry (plan/fusion_join) instead
        # of rebuilding the claim table per batch
        from bodo_tpu.plan import fusion_join
        fusion_join.prime_build(self.build, self.right_on, self.null_equal)

    def __call__(self, batch: Table) -> Table:
        out = R.join_tables(batch, self.build, self.left_on, self.right_on,
                            self.how, self.suffixes,
                            null_equal=self.null_equal)
        if out.distribution != ONED:
            out = out.shard()
        cap = _pow2_cap(max(int(out.counts.max(initial=0)), 1))
        return shard_recapacity(out, cap)


def build_stream_sharded(node, mesh=None) -> Optional[Iterator[Table]]:
    """Compile a plan subtree into a 1D batch iterator, or None when a
    node has no sharded streaming form."""
    from bodo_tpu.plan import logical as L
    m = mesh or mesh_mod.get_mesh()
    batch_rows = config.streaming_batch_size

    if isinstance(node, L.ReadParquet):
        return parquet_batches_sharded(node.path, node.columns, batch_rows,
                                       m)
    if isinstance(node, L.ReadCsv):
        return csv_batches_sharded(node.path, node.columns,
                                   node.parse_dates, batch_rows, m)
    if isinstance(node, L.FromPandas):
        t = node.table
        if t.distribution != ONED:
            if t.nrows < mesh_mod.num_shards(m):
                return None
            t = t.shard()
        return table_batches_sharded(t, max(batch_rows //
                                            mesh_mod.num_shards(m), 128), m)
    if isinstance(node, (L.Filter, L.Projection)):
        # whole-stage fusion over 1D batches: one shard_map program per
        # chain with a single count sync, instead of per-stage dispatch
        from bodo_tpu.plan import fusion
        chain = fusion.stream_chain(node)
        if chain is not None:
            steps, src = chain
            inner = build_stream_sharded(src, m)
            if inner is None:
                return None
            out = fusion.fused_batches(steps, inner, sharded=True)
            if any(isinstance(s, L.Filter) for s in steps):
                from bodo_tpu.plan import adaptive
                out = adaptive.coalesce_batches(out, sharded=True)
            return out
    if isinstance(node, L.Filter):
        inner = build_stream_sharded(node.child, m)
        if inner is None:
            return None
        pred = node.predicate

        def gen_filter(src):
            for b in src:
                yield R.filter_table(b, pred)
        # coalesce undersized post-filter 1D batches (device-side
        # append_sharded) before the next per-batch collective
        from bodo_tpu.plan import adaptive
        return adaptive.coalesce_batches(gen_filter(inner), sharded=True)
    if isinstance(node, L.Projection):
        inner = build_stream_sharded(node.child, m)
        if inner is None:
            return None
        from bodo_tpu.plan.physical import apply_projection
        exprs = node.exprs

        def gen_project(src):
            for b in src:
                yield apply_projection(b, exprs)
        return gen_project(inner)
    if isinstance(node, L.Join):
        if node.how not in ("inner", "left"):
            return None
        inner = build_stream_sharded(node.left, m)
        if inner is None:
            return None

        def _keys_streamable() -> bool:
            # Key dtypes must agree exactly (the stream skips
            # join_tables' promotion step) and string keys need a shared
            # dictionary — whole-table path otherwise.
            return not any(
                node.left.schema[lk] is not node.right.schema[rk]
                or node.left.schema[lk] is dt.STRING
                for lk, rk in zip(node.left_on, node.right_on))

        def _pjoin_gen(pj, src):
            # close() in finally: an abandoned consumer (generator GC'd
            # before exhaustion) must not leak parked host-pool chunks
            try:
                for b in src:
                    out = pj.probe(b)
                    if out is not None:
                        yield out
                yield from pj.drain()
            finally:
                pj.close()

        # stream the build side too when its subtree has a streaming
        # form: batches buffer (device) only up to the broadcast
        # threshold, then switch to the partitioned join — the build is
        # never fully materialized (reference: build streamed from the
        # scan into partitions, bodo/libs/streaming/_join.h:267).
        build_src = build_stream_sharded(node.right, m)
        if build_src is not None and _keys_streamable():
            try:
                pj = ShardedPartitionedJoin(
                    node.left_on, node.right_on, node.how, node.suffixes,
                    node.null_equal, m)
            except NotImplementedError:
                return None
            buffered: Optional[Table] = None
            nbb = 0
            for bb in build_src:
                nbb += 1
                if pj.state is not None or pj.spilling:
                    if not pj.push_build(bb):
                        return None
                    continue
                if not _dicts_compatible(buffered, bb):
                    return None
                buffered = append_sharded(buffered, bb, m)
                if buffered.nrows > config.bcast_join_threshold:
                    if not pj.push_build(buffered):
                        return None
                    buffered = None
            if pj.state is not None or pj.spilling:
                log(1, f"streaming partitioned join: build streamed over "
                       f"{nbb} batches"
                       + (f", {len(pj.build_chunks)} spilled chunks"
                          if pj.spilling else ""))
                return _pjoin_gen(pj, inner)
            if buffered is None:
                pj.close()
                return None  # empty build stream
            pj.close()  # build fit under the broadcast threshold
            log(1, f"streaming join: build streamed over {nbb} batches "
                   f"({buffered.nrows} rows, broadcast)")
            join = ShardedStreamJoin(buffered, node.left_on,
                                     node.right_on, node.how,
                                     node.suffixes, node.null_equal)

            def gen_join_s(src):
                for b in src:
                    yield join(b)
            return gen_join_s(inner)

        from bodo_tpu.plan import physical
        build = physical._exec(node.right)
        if build.nrows > config.bcast_join_threshold:
            if not _keys_streamable():
                return None
            try:
                pj = ShardedPartitionedJoin(
                    node.left_on, node.right_on, node.how, node.suffixes,
                    node.null_equal, m)
            except NotImplementedError:
                return None
            bt = build if build.distribution == ONED else build.shard()
            nbb = 0
            for bb in table_batches_sharded(
                    bt, max(batch_rows // mesh_mod.num_shards(m), 128),
                    m):
                if not pj.push_build(bb):
                    return None
                nbb += 1
            if pj.state is None and not pj.spilling:
                pj.close()
                return None
            log(1, f"streaming partitioned join: build state over "
                   f"{nbb} batches")
            return _pjoin_gen(pj, inner)
        join = ShardedStreamJoin(build, node.left_on, node.right_on,
                                 node.how, node.suffixes, node.null_equal)

        def gen_join(src):
            for b in src:
                yield join(b)
        return gen_join(inner)
    return None


def try_stream_execute_sharded(node) -> Optional[Table]:
    """Streaming executor over the full mesh: groupby plans stream 1D
    batches through the overlapped-shuffle accumulator. None → caller
    falls back to whole-table execution."""
    from bodo_tpu.plan import adaptive
    from bodo_tpu.plan import logical as L
    if not config.stream_exec:
        return None
    from bodo_tpu.runtime.resilience import maybe_inject
    maybe_inject("stage.boundary")
    m = mesh_mod.get_mesh()
    if mesh_mod.num_shards(m) <= 1:
        return None

    if isinstance(node, L.Aggregate):
        if any(dt.is_decimal(node.child.schema[c])
               for c, _, _ in node.aggs):
            return None
        if any(op not in DECOMPOSE for _, op, _ in node.aggs):
            return None
        if not node.keys:
            return None
        src = build_stream_sharded(node.child, m)
        if src is None:
            return None
        try:
            acc = ShardedGroupbyAccumulator(node.keys, node.aggs, m)
        except NotImplementedError:
            return None
        from bodo_tpu.plan.streaming import _note_batch
        nb = 0
        for b in src:
            adaptive.observe_batch(b)
            acc.push(b)
            nb += 1
            _note_batch()
        if acc._template is None:
            acc._grant.release()
            return None
        out = acc.finish()
        log(1, f"sharded streaming groupby: {nb} batches, "
               f"{out.nrows} groups over {acc.S} shards")
        return out

    if isinstance(node, L.Sort):
        # stream batches into 1D state (one pass over the child), then
        # one range exchange + local sort over the accumulated state
        src1 = build_stream_sharded(node.child, m)
        if src1 is None:
            return None
        from bodo_tpu.plan.streaming import _note_batch
        ss = ShardedStreamSort(node.by, node.ascending, node.na_last, m)
        nb = 0
        for b in src1:
            adaptive.observe_batch(b)
            if not ss.push(b):
                return None  # dict drift across batches: whole-table
            nb += 1
            _note_batch()
        if ss.state is None and not ss.runs:
            ss.close()
            return None
        out = ss.finish()
        log(1, f"sharded streaming sort: {nb} batches, {out.nrows} rows "
               f"over {ss.S} shards")
        return out

    return None


# ---------------------------------------------------------------------------
# per-shard append (shared by streaming join build state and sort state)
# ---------------------------------------------------------------------------

@cached_builder("streaming")
def _build_append(mesh_key, state_cap: int, batch_cap: int, new_cap: int):
    """shard_map kernel: place a packed batch block after the packed
    state block inside a [new_cap] buffer (per shard, no host transit)."""
    mesh = _MESHES[mesh_key]
    ax = config.data_axis

    def body(sflat, bflat, scnt, bcnt):
        s0, b0 = scnt[0], bcnt[0]
        out = []
        for sa, ba in zip(sflat, bflat):
            z = sa
            if new_cap > state_cap:
                pad = jnp.zeros((new_cap - state_cap,) + sa.shape[1:],
                                sa.dtype)
                z = jnp.concatenate([z, pad])
            idx = jnp.arange(batch_cap) + s0
            idx = jnp.where(jnp.arange(batch_cap) < b0, idx, new_cap)
            out.append(z.at[idx].set(ba, mode="drop"))
        return tuple(out), (s0 + b0)[None]

    return named_jit("stream_append", C.smap(
        body, in_specs=(P(ax), P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax)), mesh=mesh))


def append_sharded(state: Optional[Table], batch: Table,
                   mesh=None) -> Table:
    """Append a 1D batch to a 1D state table per shard (device-side).

    Capacity grows in power-of-two steps so the jitted append kernel is
    reused across pushes. Column schemas must match; string columns must
    share the state's dictionary (the streaming gate checks this)."""
    m = mesh or mesh_mod.get_mesh()
    if state is None:
        cap = _pow2_cap(max(int(batch.counts.max(initial=0)), 1))
        return shard_recapacity(batch, cap, m)
    assert state.names == batch.names, (state.names, batch.names)
    need = int((state.counts + batch.counts).max(initial=0))
    new_cap = state.shard_capacity
    if need > new_cap:
        new_cap = _pow2_cap(need)
    names = state.names
    sflat, slots = [], []
    bflat = []
    for n in names:
        sc, bc = state.column(n), batch.column(n)
        # schema drift guard: a batch dtype WIDER than the state's would
        # wrap silently under astype (int64→int32), contradicting the
        # "column schemas must match" contract — fail loudly instead
        if bc.data.dtype != sc.data.dtype and not np.can_cast(
                bc.data.dtype, sc.data.dtype, casting="safe"):
            raise ValueError(
                f"append_sharded: batch column {n!r} dtype "
                f"{bc.data.dtype} does not safely cast to state dtype "
                f"{sc.data.dtype}")
        sflat.append(sc.data)
        bflat.append(bc.data.astype(sc.data.dtype))
        has_v = sc.valid is not None or bc.valid is not None
        slots.append(has_v)
        if has_v:
            per_s, per_b = state.shard_capacity, batch.shard_capacity
            sflat.append(sc.valid if sc.valid is not None
                         else jnp.ones(per_s * state.num_shards, bool))
            bflat.append(bc.valid if bc.valid is not None
                         else jnp.ones(per_b * batch.num_shards, bool))
    fn = _build_append(_mesh_key(m), state.shard_capacity,
                       batch.shard_capacity, new_cap)
    out, cnts = fn(tuple(sflat), tuple(bflat), state.counts_device(),
                   batch.counts_device())
    counts = np.asarray(tracing.host_read(  # dispatch-boundary
        "stream.append_count", cnts)).reshape(-1).astype(np.int64)
    cols: Dict[str, Column] = {}
    j = 0
    for n, has_v in zip(names, slots):
        sc = state.column(n)
        d = out[j]
        j += 1
        v = None
        if has_v:
            v = out[j].astype(bool)
            j += 1
        cols[n] = Column(d, v, sc.dtype, sc.dictionary, None)
    return Table(cols, int(counts.sum()), ONED, counts)


def _dict_template(t: Table) -> Dict:
    """Per-column dictionary snapshot; survives state parks so drift
    detection stays live across spilled chunks."""
    return {n: t.column(n).dictionary for n in t.names}


def _dicts_match_template(tmpl: Optional[Dict], batch: Table) -> bool:
    if tmpl is None:
        return True
    for n, sd in tmpl.items():
        bd = batch.column(n).dictionary
        if sd is None and bd is None:
            continue
        if sd is None or bd is None:
            return False
        if sd is not bd and not (len(sd) == len(bd)
                                 and bool(np.all(sd == bd))):
            return False
    return True


def _dicts_compatible(state: Optional[Table], batch: Table) -> bool:
    if state is None:
        return True
    return _dicts_match_template(_dict_template(state), batch)


# ---------------------------------------------------------------------------
# host-roundtrip helpers for spilled streaming state
# ---------------------------------------------------------------------------

def _table_device_bytes(t: Table) -> int:
    n = 0
    for c in t.columns.values():
        n += c.data.size * c.data.dtype.itemsize
        if c.valid is not None:
            n += c.valid.size
    return n


def _host_cols(t: Table):
    """(data, valid) numpy copies of the live rows of a REP table."""
    host = tracing.host_read(  # dispatch-boundary
        "stream.spill_copy", t.device_data())
    return {n: (np.asarray(d)[:t.nrows],
                np.asarray(v)[:t.nrows] if v is not None else None)
            for n, (d, v) in host.items()}


def _table_from_host(host_cols, template: Table, nrows: int) -> Table:
    """REP device table from numpy columns, schema from `template`."""
    from bodo_tpu.table.table import round_capacity
    cap = round_capacity(max(nrows, 1))
    cols: Dict[str, Column] = {}
    for n, (d, v) in host_cols.items():
        src = template.column(n)
        pd_ = np.zeros((cap,), dtype=d.dtype)
        pd_[:nrows] = d
        pv = None
        if v is not None:
            pv = np.zeros((cap,), dtype=bool)
            pv[:nrows] = v
            pv = jnp.asarray(pv)
        cols[n] = Column(jnp.asarray(pd_), pv, src.dtype, src.dictionary)
    return Table(cols, nrows, REP, None)


def _concat_host_frames(frames: Sequence[Dict], template: Table,
                        nrows: int) -> Table:
    """Concatenate host-col dicts (np) into one REP device table."""
    cat = {}
    for n in template.names:
        has_v = any(f[n][1] is not None for f in frames)
        d = np.concatenate([f[n][0] for f in frames])
        v = (np.concatenate([f[n][1] if f[n][1] is not None
                             else np.ones(len(f[n][0]), bool)
                             for f in frames]) if has_v else None)
        cat[n] = (d, v)
    return _table_from_host(cat, template, nrows)


def _concat_tables_host(tables: Sequence[Table]) -> Table:
    """Concatenate REP tables host-side (np), preserving schema."""
    if len(tables) == 1:
        return tables[0]
    return _concat_host_frames([_host_cols(t) for t in tables],
                               tables[0], sum(t.nrows for t in tables))


def _host_filter(t: Table, mask: np.ndarray) -> Table:
    """Select rows of a REP table by a host bool mask (np gather)."""
    hc = _host_cols(t)
    out = {n: (d[mask], None if v is None else v[mask])
           for n, (d, v) in hc.items()}
    return _table_from_host(out, t, int(mask.sum()))


def _key_membership(p: Table, b: Table, left_on, right_on,
                    null_equal: bool) -> np.ndarray:
    """Host bool[p.nrows]: does each probe row's key appear in b?

    Scatter-claim membership probe (ops/hashtable.py); pathological
    probe-round exhaustion falls back to a pandas merge indicator."""
    from bodo_tpu.ops import hashtable as HT
    from bodo_tpu.ops import kernels as K

    pk = [(p.column(lk).data, p.column(lk).valid) for lk in left_on]
    bk = [(b.column(rk).data, b.column(rk).valid) for rk in right_on]
    pcodes, bcodes, p_ok0, b_ok0 = HT.aligned_codes(pk, bk, null_equal)
    b_pad = K.row_mask(jnp.asarray(b.nrows), b.capacity)
    p_pad = K.row_mask(jnp.asarray(p.nrows), p.capacity)
    b_ok = b_pad if b_ok0 is None else (b_pad & b_ok0)
    p_ok = p_pad if p_ok0 is None else (p_pad & p_ok0)
    T = HT.table_size(b.capacity)
    slot, owner, _r, un1 = HT.claim_slots(bcodes, b_ok, T)
    idx, un2 = HT.probe_slots(bcodes, owner, pcodes, p_ok, T)
    if bool(tracing.host_read(  # dispatch-boundary
            "stream.membership_unresolved", un1 | un2)):
        log(1, "stream join drain: membership probe-round exhaustion — "
               f"falling back to host pandas merge ({p.nrows} probe x "
               f"{b.nrows} build rows leave the device)")
        with tracing.event("host_membership_fallback") as ev:
            pl = p.select(list(left_on)).to_pandas()
            bl = b.select(list(right_on)).to_pandas().drop_duplicates()
            m = pl.merge(bl, left_on=list(left_on),
                         right_on=list(right_on),
                         how="left", indicator=True)
            matched = (m["_merge"] == "both").to_numpy()
            if not null_equal:
                matched &= ~pl.isna().any(axis=1).to_numpy()
            if ev is not None:
                ev["rows"] = p.nrows
        return matched
    return np.asarray(tracing.host_read(  # dispatch-boundary
        "stream.membership", idx))[:p.nrows] >= 0


# ---------------------------------------------------------------------------
# streaming partitioned hash join (build side too big to broadcast)
# ---------------------------------------------------------------------------

class ShardedPartitionedJoin:
    """Streaming partitioned hash join over the mesh: build batches are
    hash-shuffled to owner shards and appended into per-shard build
    state; probe batches shuffle by the same key hash and join locally
    against the accumulated state (co-partitioned by construction).

    TPU redesign of the reference's partitioned streaming hash join
    (bodo/libs/streaming/_join.h:892 HashJoinState: partitioned build
    table + per-batch probe): partitions are mesh shards, the MPI
    alltoallv is a fixed-capacity lax.all_to_all, and the per-shard
    probe is the static-shape join_local kernel under shard_map."""

    def __init__(self, left_on, right_on, how, suffixes,
                 null_equal: bool = True, mesh=None):
        if how not in ("inner", "left"):
            raise NotImplementedError(how)
        self.left_on, self.right_on = list(left_on), list(right_on)
        self.how, self.suffixes = how, suffixes
        self.null_equal = null_equal
        self.mesh = mesh or mesh_mod.get_mesh()
        self.state: Optional[Table] = None
        # larger-than-device build: when the accumulated build state
        # exceeds the governed device budget, whole state chunks park
        # into the spillable host pool; probe batches are then deferred
        # (parked too) and drained chunk-against-chunk at the end —
        # device memory stays bounded by ~2 chunks + one join output
        # (reference analogue: JoinPartition build spill + probe-side
        # chunk replay, bodo/libs/streaming/_join.h:267). The budget is
        # an admission-control grant from the memory governor (the
        # legacy stream_device_budget_mb override wins when set).
        from bodo_tpu.runtime.memory_governor import governor
        self._grant = governor().admit("stream_join")
        self.budget = self._grant.budget
        self.build_chunks: List = []    # OffloadedTable (REP row order)
        self.probe_chunks: List = []
        self._pending_probe: Optional[Table] = None
        self._key_template: Optional[Dict] = None
        self._build_dicts: Optional[Dict] = None   # survives state parks
        self._probe_dicts: Optional[Dict] = None
        self._comp = None
        self._op = None

    # -- spill plumbing -----------------------------------------------------

    def _park(self, t: Table):
        from bodo_tpu.runtime.comptroller import default_comptroller
        if self._comp is None:
            self._comp = default_comptroller()
            self._op = self._comp.register("stream_join")
        return self._comp.park(self._op, t.gather()
                               if t.distribution == ONED else t)

    @property
    def spilling(self) -> bool:
        return bool(self.build_chunks)

    def push_build(self, b: Table) -> bool:
        """Accumulate one 1D build batch. False → caller must abandon
        streaming (incompatible batch dictionaries)."""
        if b.distribution != ONED:
            b = b.shard()
        sb = R.shuffle_by_key(b, self.right_on)
        if not _dicts_match_template(self._build_dicts, sb):
            self.close()  # free any parked chunks before the fallback
            return False
        if self._build_dicts is None:
            self._build_dicts = _dict_template(sb)
            self._key_template = {
                rk: (sb.column(rk).dtype, sb.column(rk).dictionary)
                for rk in self.right_on}
        self.state = append_sharded(self.state, sb, self.mesh)
        nbytes = _table_device_bytes(self.state)
        if self._grant.over_budget(nbytes):
            self.build_chunks.append(self._park(self.state))
            self._grant.record_spill(nbytes)
            self.state = None
        return True

    def close(self) -> None:
        """Free parked host-pool state (idempotent). Called when
        streaming is abandoned or after drain() — parked chunks must not
        outlive the operator."""
        for ot in self.build_chunks + self.probe_chunks:
            try:
                ot.free()
            except Exception:
                pass
        self.build_chunks, self.probe_chunks = [], []
        self._pending_probe = None
        self.state = None
        if self._comp is not None:
            self._comp.unregister(self._op)
            self._comp = None
        self._grant.release()

    def _probe_keys_compatible(self, pb: Table) -> None:
        """Fail loudly when probe key columns cannot be compared against
        the build state raw (shuffle + local join compare dict CODES):
        drifting string dictionaries or dtype mismatch would otherwise
        return silently wrong matches for a direct user of this class
        (build_stream_sharded gates this, __graft_entry__-style callers
        don't)."""
        if self._key_template is None:
            return
        for lk, rk in zip(self.left_on, self.right_on):
            pc = pb.column(lk)
            bdt, bd = self._key_template[rk]
            if pc.dtype is not bdt:
                raise ValueError(
                    f"probe key {lk!r} dtype {pc.dtype} != build key "
                    f"{rk!r} dtype {bdt}")
            pd_ = pc.dictionary
            if pd_ is None and bd is None:
                continue
            if pd_ is None or bd is None or (
                    pd_ is not bd and not (len(pd_) == len(bd)
                                           and bool(np.all(pd_ == bd)))):
                raise ValueError(
                    f"probe key {lk!r} string dictionary differs from "
                    "build state's — codes are not comparable (re-encode "
                    "or use the whole-table join)")

    def probe(self, b: Table) -> Optional[Table]:
        """Join one probe batch. Returns the joined batch — or None when
        the build side spilled past the device budget: the batch is
        parked and its results come from drain() instead."""
        if b.distribution != ONED:
            b = b.shard()
        self._probe_keys_compatible(b)
        if self.spilling:
            # defer RAW batches (no shuffle: drain()'s join_tables
            # re-partitions restored chunks from scratch anyway)
            if not _dicts_match_template(self._probe_dicts, b):
                raise ValueError("probe batch dictionaries drifted "
                                 "across spilled streaming state")
            if self._probe_dicts is None:
                self._probe_dicts = _dict_template(b)
            self._pending_probe = append_sharded(self._pending_probe, b,
                                                 self.mesh)
            nbytes = _table_device_bytes(self._pending_probe)
            if self._grant.over_budget(nbytes):
                self.probe_chunks.append(self._park(self._pending_probe))
                self._grant.record_spill(nbytes)
                self._pending_probe = None
            return None
        pb = R.shuffle_by_key(b, self.left_on)
        out = R._join_sharded(pb, self.state, self.left_on, self.right_on,
                              self.how, self.suffixes,
                              null_equal=self.null_equal,
                              pre_shuffled=True)
        cap = _pow2_cap(max(int(out.counts.max(initial=0)), 1))
        return shard_recapacity(out, cap, self.mesh)

    def drain(self) -> Iterator[Table]:
        """Emit results for probe batches deferred while the build side
        was spilled: every (probe chunk × build chunk) pair joins inner
        at bounded device residency; for a left join, probe rows matched
        by NO chunk emit once against an empty build table (preserving
        output schema/suffix naming). Frees all parked state."""
        if not self.spilling:
            return
        if self.state is not None:
            self.build_chunks.append(self._park(self.state))
            self.state = None
        if self._pending_probe is not None:
            self.probe_chunks.append(self._park(self._pending_probe))
            self._pending_probe = None
        log(1, f"streaming join drain: {len(self.build_chunks)} build x "
               f"{len(self.probe_chunks)} probe spilled chunks")
        try:
            for pot in self.probe_chunks:
                p = pot.restore_slice(0, pot.nrows)
                matched = np.zeros(p.nrows, dtype=bool)
                empty_b = None
                for bot in self.build_chunks:
                    c = bot.restore_slice(0, bot.nrows)
                    out = R.join_tables(
                        p.shard(), c.shard(), self.left_on, self.right_on,
                        how="inner", suffixes=self.suffixes,
                        null_equal=self.null_equal)
                    if out.distribution != ONED:
                        out = out.shard()
                    yield out
                    if self.how == "left":
                        matched |= _key_membership(
                            p, c, self.left_on, self.right_on,
                            self.null_equal)
                    if empty_b is None:
                        zc = np.zeros(mesh_mod.num_shards(self.mesh),
                                      dtype=np.int64)
                        cb = c.shard()
                        empty_b = Table(dict(cb.columns), 0, ONED, zc)
                if self.how == "left" and not matched.all():
                    unm = _host_filter(p, ~matched)
                    out = R.join_tables(
                        unm.shard(), empty_b, self.left_on, self.right_on,
                        how="left", suffixes=self.suffixes,
                        null_equal=self.null_equal)
                    if out.distribution != ONED:
                        out = out.shard()
                    yield out
        finally:
            self.close()


# ---------------------------------------------------------------------------
# streaming sample sort (two passes over a re-buildable stream)
# ---------------------------------------------------------------------------

class ShardedStreamSort:
    """Distributed streaming sort with run-generation external sort.

    Batches append into per-shard 1D state as they flow (one pass over
    the child). Under a device budget (config.stream_device_budget_mb),
    each time the state exceeds the budget it is SORTED into a run and
    parked in the spillable host pool (the comptroller spills runs to
    disk under pressure); finish() then range-merges the sorted runs:
    global range splitters come from the runs' partition keys (host),
    each range restores only its row slices from every run (binary
    search on the runs' sorted keys — no full-run restore), concatenates
    and locally sorts them, so device residency during the merge is one
    range at a time.

    The reference streams sort chunks with spill + final k-way merge
    (bodo/libs/streaming/_sort.cpp external sort); the k-way comparator
    merge becomes a range-partitioned re-sort, the same trade the mesh
    sample sort makes (ops/sort.py). With no budget (0), finish() is the
    one-shot mesh sample sort over the accumulated state."""

    def __init__(self, by, ascending, na_last: bool, mesh=None):
        self.by = list(by)
        self.ascending = list(ascending)
        self.na_last = na_last
        self.mesh = mesh or mesh_mod.get_mesh()
        self.S = mesh_mod.num_shards(self.mesh)
        self.state: Optional[Table] = None
        from bodo_tpu.runtime.memory_governor import governor
        self._grant = governor().admit("stream_sort")
        self.budget = self._grant.budget
        self.runs: List[Tuple] = []  # (OffloadedTable, pk np, nbytes)
        self._dicts: Optional[Dict] = None  # survives run parks
        self._comp = None
        self._op = None

    def push(self, b: Table) -> bool:
        if b.distribution != ONED:
            b = b.shard()
        if not _dicts_match_template(self._dicts, b):
            self.close()
            return False
        if self._dicts is None:
            self._dicts = _dict_template(b)
        self.state = append_sharded(self.state, b, self.mesh)
        if self._grant.over_budget(_table_device_bytes(self.state)):
            self._park_run()
        return True

    def close(self) -> None:
        """Free parked runs (idempotent) — abandonment must not leak."""
        for ot, _pk, _b in self.runs:
            try:
                ot.free()
            except Exception:
                pass
        self.runs = []
        self.state = None
        if self._comp is not None:
            self._comp.unregister(self._op)
            self._comp = None
        self._grant.release()

    def _park_run(self) -> None:
        from bodo_tpu.ops.sort import _partition_key
        from bodo_tpu.runtime.comptroller import default_comptroller
        if self._comp is None:
            self._comp = default_comptroller()
            self._op = self._comp.register("stream_sort")
        run = R.sort_table(self.state, self.by, self.ascending,
                           self.na_last)
        g = run.gather() if run.distribution == ONED else run
        c0 = g.column(self.by[0])
        padmask = jnp.arange(g.capacity) < g.nrows
        pk = _partition_key([(c0.data, c0.valid)], [self.ascending[0]],
                            self.na_last, padmask)
        pk = np.asarray(tracing.host_read(  # dispatch-boundary
            "stream.partition_key", pk))[:g.nrows]
        nbytes = _table_device_bytes(g)
        ot = self._comp.park(self._op, g)
        self.runs.append((ot, pk, nbytes))
        self._grant.record_spill(nbytes)
        self.state = None
        log(1, f"streaming sort: parked run {len(self.runs)} "
               f"({g.nrows} rows, {nbytes >> 20} MiB)")

    def finish(self) -> Table:
        if not self.runs:
            out = R.sort_table(self.state, self.by, self.ascending,
                               self.na_last)
            self.close()
            return out
        if self.state is not None and self.state.nrows > 0:
            self._park_run()
        try:
            return self._merge_runs()
        finally:
            self.close()

    def _merge_runs(self) -> Table:
        total_rows = sum(pk.size for _ot, pk, _b in self.runs)
        total_bytes = sum(b for *_x, b in self.runs)
        nranges = max(2, -(-total_bytes // max(self.budget, 1)))
        allpk = np.sort(np.concatenate([pk for _ot, pk, _b in self.runs]))
        spl = [allpk[min(i * total_rows // nranges, total_rows - 1)]
               for i in range(1, nranges)]
        log(1, f"streaming sort merge: {len(self.runs)} runs, "
               f"{total_rows} rows, {nranges} ranges")
        frames = []
        template = None
        out_rows = 0
        for r in range(nranges):
            parts = []
            for ot, pk, _b in self.runs:
                lo = 0 if r == 0 else int(np.searchsorted(
                    pk, spl[r - 1], side="left"))
                hi = pk.size if r == nranges - 1 else int(np.searchsorted(
                    pk, spl[r], side="left"))
                if hi > lo:
                    parts.append(ot.restore_slice(lo, hi))
            if not parts:
                continue
            chunk = _concat_tables_host(parts)
            schunk = R.sort_table(chunk, self.by, self.ascending,
                                  self.na_last)
            if schunk.distribution == ONED:
                schunk = schunk.gather()
            frames.append(_host_cols(schunk))
            template = schunk
            out_rows += schunk.nrows
        out = _concat_host_frames(frames, template, out_rows)
        return out.shard()


