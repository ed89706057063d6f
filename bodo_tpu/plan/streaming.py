"""Streaming batch executor: batch-at-a-time pipelines with bounded
device memory.

TPU-native redesign of the reference's streaming execution model
(reference: bodo/pandas/_pipeline.h:106 Pipeline, _executor.h:76 Executor,
physical/operator.h:46 the ConsumeBatch/ProduceBatch operator protocol,
bodo/libs/streaming/_groupby.cpp GroupbyState). The C++ pull-pipeline with
NEED_MORE_INPUT/HAVE_MORE_OUTPUT states becomes a host-driven Python loop
over fixed-capacity device batches:

  - sources yield REP Tables padded to ONE static capacity, so every
    per-batch kernel (filter/project/join-probe/partial-agg) compiles
    once and is reused for the whole stream;
  - blocking operators accumulate packed *partial* state on device
    (groupby) or park batches in the native host buffer pool
    (runtime/offload.py) where they are spillable to disk (sort, join
    build sides) — device memory stays O(batch + state), not O(rows);
  - string columns ride a *running* unified dictionary so codes stay
    comparable across batches (the reference's dict-builder unification,
    bodo/libs/_dict_builder.cpp); accumulated state is re-coded on the
    rare batch that introduces new strings.

Capacities that vary at runtime (filter survivors, join fan-out) are
re-bucketed to powers of two so the compile count stays logarithmic.

v1 scope: single-shard (REP) streams — the multi-device path continues to
use the whole-table shard_map operators; streaming+shuffle overlap is the
async-shuffle milestone.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bodo_tpu import relational as R
from bodo_tpu.config import config
from bodo_tpu.ops.groupby import groupby_local, groupby_merge, result_dtype
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.parallel.shuffle import _finalize, _plan_decomposition
from bodo_tpu.plan import logical as L
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import (Column, REP, Table, round_capacity)
from bodo_tpu.utils import tracing
from bodo_tpu.utils.kernel_cache import cached_builder, named_jit
from bodo_tpu.utils.logging import log


def _bucket_cap(n: int) -> int:
    """Round capacity to a power of two (min 128) so streaming stages see
    a logarithmic number of distinct shapes."""
    c = 128
    while c < n:
        c <<= 1
    return c


# ---------------------------------------------------------------------------
# host-sync accounting
# ---------------------------------------------------------------------------
# Every host read inside a streaming step body stalls the pipeline: the
# host waits for the device instead of decoding the next batch. The
# accumulators below are written so syncs per stage are O(1)–O(log
# batches), not O(batches); each legitimate sync site is annotated
# `# dispatch-boundary` (shardcheck lints unannotated ones) and reads
# through `tracing.host_read` under a site named `stream.<what>`, so a
# test can regress on syncs-per-batch.

def _stream_syncs() -> int:
    return sum(n for site, n in tracing.sync_counts().items()
               if site.startswith("stream."))


class _StreamStats(dict):
    """`batches` is counted here; `host_syncs` is read from
    `tracing.sync_counts()`: host_read's calls at the `stream.*` sites
    since `reset_stream_stats()`."""

    def __init__(self):
        super().__init__()
        self.reset()

    def reset(self) -> None:
        self["batches"] = 0
        self._syncs_at_reset = _stream_syncs()

    def __getitem__(self, key):
        if key == "host_syncs":
            return _stream_syncs() - self._syncs_at_reset
        return super().__getitem__(key)

    def __repr__(self):
        return repr({"host_syncs": self["host_syncs"], **self})


stream_stats = _StreamStats()


def _note_batch(n: int = 1) -> None:
    stream_stats["batches"] += n


def reset_stream_stats() -> None:
    stream_stats.reset()


def _with_capacity(t: Table, cap: int) -> Table:
    """Re-capacity a packed REP table (slice down / zero-pad up)."""
    if cap == t.capacity:
        return t
    assert cap >= t.nrows, (cap, t.nrows)
    cols: Dict[str, Column] = {}
    for n, c in t.columns.items():
        if cap <= c.capacity:
            d = c.data[:cap]
            v = c.valid[:cap] if c.valid is not None else None
        else:
            pad = cap - c.capacity
            d = jnp.concatenate(
                [c.data, jnp.zeros((pad,), dtype=c.data.dtype)])
            v = None if c.valid is None else jnp.concatenate(
                [c.valid, jnp.zeros((pad,), dtype=bool)])
        cols[n] = Column(d, v, c.dtype, c.dictionary)
    return Table(cols, t.nrows, REP, None)


# ---------------------------------------------------------------------------
# running-dictionary tracker
# ---------------------------------------------------------------------------

class DictTracker:
    """Per-column running dictionaries for a stream.

    Re-encodes each batch's string columns onto the running (sorted,
    unioned) dictionary; the dictionary OBJECT stays stable while no new
    strings appear, which keeps downstream kernel caches warm."""

    def __init__(self):
        self._dicts: Dict[str, np.ndarray] = {}

    def current(self, name: str) -> Optional[np.ndarray]:
        return self._dicts.get(name)

    def absorb(self, t: Table) -> Table:
        cols = dict(t.columns)
        for name, c in t.columns.items():
            if c.dictionary is None:
                continue
            run = self._dicts.get(name)
            if run is None:
                self._dicts[name] = c.dictionary
                continue
            if c.dictionary is run:
                continue
            union = np.union1d(run, c.dictionary)
            if len(union) == len(run):
                union = run  # no new strings: keep the stable object
            else:
                self._dicts[name] = union
            cols[name] = remap_codes(c, union)
        return Table(cols, t.nrows, REP, None)


def remap_codes(c: Column, new_dict: np.ndarray) -> Column:
    """Re-encode a string column's codes onto a superset dictionary."""
    old = c.dictionary if c.dictionary is not None else np.array([], str)
    if new_dict is old:
        return c
    lut = np.searchsorted(new_dict, old).astype(np.int32)
    mp = jnp.asarray(lut if len(lut) else np.zeros(1, np.int32))
    data = mp[jnp.clip(c.data, 0, max(len(old) - 1, 0))]
    return Column(data, c.valid, c.dtype, new_dict)


# ---------------------------------------------------------------------------
# batch sources
# ---------------------------------------------------------------------------

def parquet_batches(path: str, columns: Optional[Sequence[str]],
                    batch_rows: int) -> Iterator[Table]:
    """Stream a parquet dataset as fixed-capacity REP Tables (the
    reference's ArrowReader streaming read, bodo/io/arrow_reader.h:170).

    Each raw iter_batches pull runs under the retry envelope (the
    `io.read` fault point fires per pull, so armed faults surface on
    whatever thread consumes this generator — including a Prefetcher
    worker — and transient flakes retry in place). Re-slicing to the
    fixed batch size goes through slice_arrow_batches, which is linear:
    the pending tail concatenates once per input chunk instead of
    rebuilding pa.Table.from_batches per carried-over row group."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from bodo_tpu.io.arrow_bridge import arrow_to_table
    from bodo_tpu.io.csv import slice_arrow_batches
    from bodo_tpu.io.parquet import _dataset_files, _opened, footer_metadata
    from bodo_tpu.runtime import resilience

    cap = round_capacity(batch_rows)
    tracker = DictTracker()
    cols = list(columns) if columns else None
    _END = object()

    from bodo_tpu.config import config
    _units = [(f, rg) for f in _dataset_files(path)
              for rg in range(footer_metadata(f).num_row_groups)]
    if getattr(config, "device_decode", False):
        from bodo_tpu.io import device_decode as _dd
    else:
        _dd = None
    if _dd is not None and _dd.worth_device_decode(_units):
        # device route: pool-side raw-page bundles (prefetched BYTES,
        # admission charged at compressed+decoded size via
        # RawRowGroup.nbytes) decode on-chip at the consumer, then
        # re-slice to the fixed batch capacity. Per-pull retry lives
        # inside raw_bundles; unsupported columns fall back per column
        # inside decode, so this route never rejects a dataset.
        from bodo_tpu.runtime.io_pool import prefetched

        bundles = prefetched(_dd.raw_bundles(path, cols, units=_units),
                             label="parquet_raw")
        for b in _dd.decoded_batches(bundles, batch_rows):
            dd_flag = getattr(b, "_device_decoded", False)
            b = tracker.absorb(b)
            b._device_decoded = dd_flag
            yield b
        return

    def raw() -> Iterator[pa.Table]:
        for f in _dataset_files(path):
            with _opened(f) as src:
                pf = pq.ParquetFile(src, metadata=footer_metadata(f))
                it = pf.iter_batches(batch_size=batch_rows, columns=cols)
                while True:
                    rb = resilience.retry_call(
                        lambda: next(it, _END),
                        label="parquet_batch", point="io.read")
                    if rb is _END:
                        break
                    yield pa.Table.from_batches([rb])

    for at in slice_arrow_batches(raw(), batch_rows):
        yield tracker.absorb(arrow_to_table(at, capacity=cap))


def csv_batches(path: str, columns: Optional[Sequence[str]],
                parse_dates, batch_rows: int) -> Iterator[Table]:
    """Stream a CSV file as fixed-capacity REP Tables: newline-aligned
    byte-range chunks parsed one at a time (bounded host memory), then
    re-sliced to a fixed row count so every downstream kernel compiles
    once (reference: chunked parallel CSV read,
    bodo/io/_csv_json_reader.cpp + csv_iterator_ext.py)."""
    from bodo_tpu.io.arrow_bridge import arrow_to_table
    from bodo_tpu.io.csv import iter_csv_arrow, slice_arrow_batches

    cap = round_capacity(batch_rows)
    tracker = DictTracker()
    for at in slice_arrow_batches(
            iter_csv_arrow(path, columns, parse_dates), batch_rows):
        yield tracker.absorb(arrow_to_table(at, capacity=cap))


def table_batches(t: Table, batch_rows: int) -> Iterator[Table]:
    """Slice an in-memory REP table into fixed-capacity batches (static
    Python slice bounds, so every batch shares one compiled shape)."""
    assert t.distribution == REP
    cap = round_capacity(batch_rows)
    n = t.nrows

    def slice_pad(a, off):
        piece = a[off:min(off + cap, a.shape[0])]
        if piece.shape[0] < cap:
            piece = jnp.concatenate(
                [piece, jnp.zeros((cap - piece.shape[0],), piece.dtype)])
        return piece

    for off in range(0, max(n, 1), batch_rows):
        take = max(0, min(batch_rows, n - off))
        cols: Dict[str, Column] = {}
        for name, c in t.columns.items():
            cols[name] = Column(
                slice_pad(c.data, off),
                slice_pad(c.valid, off) if c.valid is not None else None,
                c.dtype, c.dictionary)
        yield Table(cols, take, REP, None)
        if n == 0:
            break


# ---------------------------------------------------------------------------
# blocking operators
# ---------------------------------------------------------------------------

class GroupbyAccumulator:
    """Streaming groupby: per-batch local partial aggregation merged into
    a packed device state (reference: GroupbyState::UpdateGroupsAndCombine,
    bodo/libs/streaming/_groupby.cpp). State is O(distinct groups).

    Pipelined (the async-overlap milestone): push() only DISPATCHES the
    partial aggregation — group counts stay on device as traced scalars,
    and merges size their static capacities from host-known row-count
    BOUNDS, so no host sync sits between batches. The device works on
    batch k's merge while the host decodes batch k+1 (the reference gets
    the same overlap from IncrementalShuffleState's async sends,
    bodo/libs/streaming/_shuffle.h:777).

    Sync schedule is GEOMETRIC: the k-th capacity-tightening sync lands
    after SYNC_EVERY·2^k merges, so a B-batch stream costs O(log B) host
    round-trips total (a fixed interval would cost O(B)). Between syncs
    the host bound creeps by at most the interval's batch rows, and each
    sync snaps both the bound and the state capacity back to the actual
    group count — capacity stays within one doubling of what a per-batch
    sync would keep."""

    SYNC_EVERY = 4  # first sync interval; doubles after every sync

    def __init__(self, keys: Sequence[str], aggs: Sequence[Tuple]):
        self.keys = list(keys)
        self.aggs = list(aggs)
        specs = tuple(op for _, op, _ in aggs)
        self.partial_specs, self.combine_specs, self.layout = \
            _plan_decomposition(specs)
        # parts per agg (layout is contiguous per spec)
        self._nparts = [len(_plan_decomposition((op,))[0])
                        for _, op, _ in self.aggs]
        self.state: Optional[Table] = None  # keys + __p{i} partial cols
        self._n_state_dev = None            # device scalar (deferred sync)
        self._bound = 0                     # host upper bound on n_state
        self._since_sync = 0
        self._sync_interval = self.SYNC_EVERY
        self._queue: List = []              # dispatched, unmerged partials
        self._template: Optional[Table] = None  # schema source
        self._grant = None                  # governor admission (lazy)

    @property
    def n_state(self) -> int:
        self._drain_all()
        if self._n_state_dev is None:
            return 0
        return int(tracing.host_read(  # dispatch-boundary
            "stream.state_count", self._n_state_dev))

    def _partial_names(self) -> List[str]:
        return [f"__p{i}" for i in range(len(self.partial_specs))]

    def push(self, batch: Table) -> None:
        nk = len(self.keys)
        if self._template is None:
            self._template = batch
        if self._grant is None:
            from bodo_tpu.runtime.memory_governor import governor
            self._grant = governor().admit("stream_groupby")
        if batch.nrows == 0 and (self.state is not None or self._queue):
            return  # empty batch (selective filter): nothing to merge
        arrays = tuple((batch.column(k).data, batch.column(k).valid)
                       for k in self.keys)
        arrays += tuple(
            (batch.column(c).data, batch.column(c).valid)
            for (c, _, _), np_ in zip(self.aggs, self._nparts)
            for _ in range(np_))
        with tracing.event("stream_partial"):
            pk, pv, ng = groupby_local(arrays, jnp.asarray(batch.nrows),
                                       self.partial_specs, batch.capacity,
                                       nk)
        ng_bound = max(min(batch.nrows, batch.capacity), 1)
        partial = self._as_state_table(batch, pk, pv, 0)
        partial = _with_capacity(partial, _bucket_cap(ng_bound))
        self._queue.append((partial, ng, ng_bound))
        # depth-1 lookahead: merge batch k while the caller decodes k+1
        while len(self._queue) > 1:
            self._drain_one()

    def _drain_all(self) -> None:
        while self._queue:
            self._drain_one()

    def _drain_one(self) -> None:
        nk = len(self.keys)
        partial, ng_dev, ng_bound = self._queue.pop(0)

        if self.state is None:
            self.state = partial
            self._n_state_dev = ng_dev
            self._bound = ng_bound
            return

        # re-code state onto any grown dictionaries before merging
        state = self.state
        cols = dict(state.columns)
        changed = False
        for name, c in state.columns.items():
            bdict = partial.columns[name].dictionary
            if c.dictionary is not None and bdict is not None and \
                    c.dictionary is not bdict:
                cols[name] = remap_codes(c, bdict)
                changed = True
        if changed:
            state = Table(cols, state.nrows, REP, None)

        out_cap = _bucket_cap(max(self._bound + ng_bound, state.capacity))
        s_arrays = tuple((state.column(n).data, state.column(n).valid)
                         for n in state.names)
        b_arrays = tuple((partial.column(n).data, partial.column(n).valid)
                         for n in state.names)
        with tracing.event("stream_merge"):
            mk, mv, ng2 = groupby_merge(s_arrays, b_arrays,
                                        self._n_state_dev, ng_dev,
                                        self.combine_specs, out_cap, nk)
        self._n_state_dev = ng2
        self._bound += ng_bound
        self._since_sync += 1
        names = state.names
        cols = {}
        for name, (d, v) in zip(names[:nk], mk):
            src = state.columns[name]
            cols[name] = Column(d, v, src.dtype, src.dictionary)
        for name, (d, v) in zip(names[nk:], mv):
            src = state.columns[name]
            cols[name] = Column(d, v, src.dtype, src.dictionary)
        # mid-stream state.nrows is the host BOUND, not the true group
        # count — the true count lives on device until the next sync
        st = Table(cols, self._bound, REP, None)

        if self._since_sync >= self._sync_interval:
            # geometric sync: tighten the bound (and the state capacity)
            # to the actual group count, then double the interval so a
            # B-batch stream pays O(log B) of these round-trips total
            n = int(tracing.host_read(  # dispatch-boundary
                "stream.group_count", ng2))
            self._bound = n
            self._since_sync = 0
            self._sync_interval *= 2
            st = Table(cols, n, REP, None)
            tight = _bucket_cap(max(n, 1))
            if tight * 2 <= st.capacity:
                st = _with_capacity(st, tight)
        self.state = st
        if self._grant is not None:
            from bodo_tpu.runtime.memory_governor import \
                table_device_bytes
            self._grant.update(table_device_bytes(st))

    def _as_state_table(self, batch: Table, pk, pv, ng: int) -> Table:
        cols: Dict[str, Column] = {}
        for name, (d, v) in zip(self.keys, pk):
            src = batch.column(name)
            cols[name] = Column(d, v, src.dtype, src.dictionary)
        pi = 0
        for (cname, op, _), nparts in zip(self.aggs, self._nparts):
            src = batch.column(cname)
            for j in range(nparts):
                pop = self.partial_specs[pi]
                d, v = pv[pi]
                if pop in ("min", "max", "first", "last"):
                    pdt, pdic = src.dtype, src.dictionary
                else:
                    pdt = dt.from_numpy(result_dtype(pop, src.dtype.numpy))
                    pdic = None
                cols[self._partial_names()[pi]] = Column(d, v, pdt, pdic)
                pi += 1
        return Table(cols, ng, REP, None)

    def finish(self) -> Table:
        nk = len(self.keys)
        n_final = self.n_state  # drains the pipeline + syncs the count
        # push() sets state on the first batch (even an all-padding one);
        # a truly batch-less stream is filtered by try_stream_execute
        assert self.state is not None
        state = self.state
        names = state.names
        pcols = [state.columns[n] for n in names[nk:]]
        finals = []
        for i, (cname, op, oname) in enumerate(self.aggs):
            off, n = self.layout[i]
            cols_in = tuple((pcols[off + j].data, pcols[off + j].valid)
                            for j in range(n))
            src_dt = self._template.column(cname).dtype
            d, v = _finalize(op, cols_in, jnp.dtype(src_dt.numpy))
            rdt = src_dt if op in ("min", "max", "first", "last") \
                else dt.from_numpy(result_dtype(op, src_dt.numpy))
            dic = pcols[off].dictionary if rdt is dt.STRING else None
            finals.append((oname, Column(d, v, rdt, dic)))
        out: Dict[str, Column] = {n: state.columns[n] for n in names[:nk]}
        for oname, col in finals:
            out[oname] = col
        if self._grant is not None:
            self._grant.release()
        return Table(out, n_final, REP, None)


class MixedGroupbyStream:
    """Streaming groupby covering non-decomposable aggregations
    (VERDICT r2 weak #5). Three strategies, mirroring the reference's
    streaming groupby modes (bodo/libs/streaming/_groupby.cpp):

    - decomposable ops: the partial/combine `GroupbyAccumulator`
      (AGG mode). A hidden `size` agg always rides along so the final
      key set covers every group.
    - nunique: a second-level decomposition — the streaming state is
      the DISTINCT (keys, value) pairs (an inner GroupbyAccumulator
      keyed on keys+value), finalized by a count per key. State stays
      O(distinct pairs), never O(rows).
    - order statistics / value-list ops (median, quantile, mode,
      listagg): no bounded exact state exists, so rows accumulate in
      the spillable host pool (the reference's ACC mode materializes
      input the same way) and the batch groupby runs at finish.

    Results of the three strategies join back on the group keys.
    """

    _ROWSTORE_OPS = ("median", "mode")

    def __init__(self, keys: Sequence[str], aggs: Sequence[Tuple]):
        self.keys = list(keys)
        self.aggs = list(aggs)
        dec, self.nun, self.acc = [], [], []
        for col, op, out in aggs:
            if op == "nunique":
                self.nun.append((col, op, out))
            elif op in self._ROWSTORE_OPS or op.startswith("quantile_") \
                    or op.startswith("listagg"):
                self.acc.append((col, op, out))
            else:
                dec.append((col, op, out))   # may still raise below
        self._hidden_size = "__msize"
        self.dec = GroupbyAccumulator(
            self.keys, dec + [(self.keys[0], "size", self._hidden_size)])
        self.nun_accs = {}
        for col, _, _ in self.nun:
            if col not in self.nun_accs:
                self.nun_accs[col] = GroupbyAccumulator(
                    self.keys + [col],
                    [(self.keys[0], "size", "__paircnt")])
        self.rows = None
        if self.acc:
            from bodo_tpu.runtime.comptroller import default_comptroller
            from bodo_tpu.runtime.memory_governor import governor
            self._comp = default_comptroller()
            self._op = self._comp.register("stream_groupby_acc")
            self._grant = governor().admit("stream_groupby_acc")
            self.rows = []
            self._acc_cols = list(dict.fromkeys(
                self.keys + [c for c, _, _ in self.acc]))

    def push(self, batch: Table) -> None:
        self.dec.push(batch)
        for acc in self.nun_accs.values():
            acc.push(batch)
        if self.rows is not None and batch.nrows:
            from bodo_tpu.runtime.memory_governor import \
                table_device_bytes
            part = _with_capacity(batch.select(self._acc_cols),
                                  _bucket_cap(max(batch.nrows, 1)))
            self.rows.append(self._comp.park(self._op, part))
            self._grant.record_spill(table_device_bytes(part))

    def finish(self) -> Table:
        base = self.dec.finish()
        for col, _, out in self.nun:
            pairs = self.nun_accs[col].finish()
            cnt = R.groupby_agg(pairs.select(self.keys + [col]),
                                self.keys, [(col, "count", out)])
            base = self._join(base, cnt, fill_zero=[out])
        if self.rows is not None:
            tables = [p.restore() for p in self.rows]
            self.rows = []
            self._comp.unregister(self._op)
            self._grant.release()
            if tables:
                full = R.concat_tables(tables) if len(tables) > 1 \
                    else tables[0]
                accres = R.groupby_agg(full, self.keys, self.acc)
                base = self._join(base, accres, fill_zero=[])
            else:
                # all batches were empty: no rows were parked, but the
                # output schema must still carry the agg columns (typed
                # all-null, matching the whole-table path)
                import jax.numpy as jnp
                for col, op, out in self.acc:
                    src = self.dec._template.column(col)
                    if op == "mode":
                        rdt, dic = src.dtype, src.dictionary
                    elif op.startswith("listagg"):
                        rdt, dic = dt.STRING, np.array([], dtype=str)
                    else:  # median / quantile_*
                        rdt, dic = dt.FLOAT64, None
                    cap = base.capacity
                    base.columns[out] = Column(
                        jnp.zeros(cap, rdt.numpy),
                        jnp.zeros(cap, bool), rdt, dic)
        order = self.keys + [out for _, _, out in self.aggs]
        return base.select([n for n in order if n in base.columns])

    def close(self) -> None:
        """Abandon (empty-stream fallback): free parked row parts."""
        if self.rows is not None:
            for p in self.rows:
                p.free()
            self.rows = []
            self._comp.unregister(self._op)
            self._grant.release()

    def _join(self, base: Table, other: Table, fill_zero) -> Table:
        from bodo_tpu.plan.expr import ColRef, Lit, UnOp, Where
        out = R.join_tables(base, other, self.keys, self.keys, "left")
        fills = {}
        for name in fill_zero:
            if name in out.columns and out.columns[name].valid is not None:
                fills[name] = Where(UnOp("isna", ColRef(name)), Lit(0),
                                    ColRef(name))
        if fills:
            out = R.assign_columns(out, fills)
        return out


_MOMENT_OPS = ("mean", "var", "std", "var0", "std0")


def _sum_acc_dtype(d):
    """Widened accumulation dtype for a sum over `d` (exact in the
    widened source family — matches relational.reduce_table)."""
    if jnp.issubdtype(d, jnp.floating):
        return jnp.float64
    if jnp.issubdtype(d, jnp.unsignedinteger):
        return jnp.uint64
    return jnp.int64


def _minmax_identity(dtype, op: str):
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf if op == "min" else -np.inf, dtype)
    if dtype == np.bool_:
        return np.array(op == "min", np.bool_)
    info = np.iinfo(dtype)
    return np.array(info.max if op == "min" else info.min, dtype)


@cached_builder("streaming")
def _build_reduce_step(sig: Tuple, cap: int, donate: bool):
    """One streamed-reduce step: per-batch masked partials folded into
    the running device carry (sums/counts add, min/max fold through
    their identities, moments combine with the exact delta-form Chan
    update). `sig` is one (op, dtype_str, has_valid) per agg; the carry
    is a flat tuple of 0-d device scalars, DONATED back to the step on
    accelerator backends so the state never holds two buffers."""
    from bodo_tpu.ops import kernels as K

    def step(carry, arrays, count):
        padmask = K.row_mask(count, cap)
        out: List = []
        ci = 0
        for (op, dstr, _hv), (d, v) in zip(sig, arrays):
            ok = K.value_ok(d, v, padmask)
            if op in _MOMENT_OPS:
                x = d.astype(jnp.float64)
                n_b = jnp.sum(ok).astype(jnp.int64)
                s_b = jnp.sum(jnp.where(ok, x, 0.0))
                nbf = jnp.maximum(n_b, 1).astype(jnp.float64)
                dd = jnp.where(ok, x - s_b / nbf, 0.0)
                m2_b = jnp.sum(dd * dd)
                n_a, s_a, m2_a = carry[ci], carry[ci + 1], carry[ci + 2]
                naf = jnp.maximum(n_a, 1).astype(jnp.float64)
                both = (n_a > 0) & (n_b > 0)
                delta = s_b / nbf - s_a / naf
                nf = n_a.astype(jnp.float64) + n_b.astype(jnp.float64)
                term = jnp.where(
                    both,
                    delta * delta * n_a.astype(jnp.float64)
                    * n_b.astype(jnp.float64) / jnp.maximum(nf, 1.0),
                    0.0)
                out += [n_a + n_b, s_a + s_b, m2_a + m2_b + term]
                ci += 3
            elif op in ("sum", "sumnull"):
                acc = carry[ci]
                x = d.astype(acc.dtype)
                s_b = jnp.sum(jnp.where(ok, x, jnp.zeros((), x.dtype)))
                out.append(acc + s_b)
                ci += 1
                if op == "sumnull":
                    out.append(carry[ci] + jnp.sum(ok).astype(jnp.int64))
                    ci += 1
            elif op in ("count", "size"):
                src = ok if op == "count" else padmask
                out.append(carry[ci] + jnp.sum(src).astype(jnp.int64))
                ci += 1
            elif op in ("min", "max"):
                ident = jnp.asarray(_minmax_identity(np.dtype(dstr), op))
                f = jnp.minimum if op == "min" else jnp.maximum
                red = jnp.min if op == "min" else jnp.max
                out.append(f(carry[ci], red(jnp.where(ok, d, ident))))
                out.append(carry[ci + 1] + jnp.sum(ok).astype(jnp.int64))
                ci += 2
            elif op == "prod":
                p_b = jnp.prod(jnp.where(ok, d.astype(jnp.float64), 1.0))
                out.append(carry[ci] * p_b)
                ci += 1
        return tuple(out)

    return named_jit(
        "reduce_stream", step, donate_argnums=(0,) if donate else ())


class ReduceAccumulator:
    """Streaming whole-column reductions with a DEVICE-RESIDENT carry.

    The old shape — per-batch `reduce_table` → host scalars → Python
    combine — forced one device round-trip per batch, serializing decode
    and compute. Now each push dispatches ONE jitted step that folds the
    batch's masked partials into the running carry on device (Chan
    delta-form combine for the moments; reference:
    bodo/libs/groupby/_groupby_update.cpp var_combine), and the carry is
    DONATED back to the step on accelerator backends
    (`donate_argnums=(0,)`) so the state never occupies two buffers.
    The host reads nothing until finish(): host syncs per stage are
    O(1), was O(batches), and decode(n+1) overlaps compute(n)."""

    _SUPPORTED = {"sum", "sumnull", "count", "size", "min", "max", "mean",
                  "var", "std", "var0", "std0", "prod"}

    def __init__(self, aggs: Sequence[Tuple[str, str, str]]):
        for _, op, _ in aggs:
            if op not in self._SUPPORTED:
                raise NotImplementedError(op)
        self.aggs = list(aggs)
        self._template: Optional[Table] = None
        self._carry: Optional[Tuple] = None  # flat 0-d device scalars
        self._sig: Optional[Tuple] = None
        self._nbatches = 0
        self._donate = jax.default_backend() in ("tpu", "gpu")
        # verify_donation verdict after the first donated step (None
        # until one runs; False on backends that silently copy)
        self.donation_verified: Optional[bool] = None

    def _init_carry(self) -> Tuple:
        slots: List = []
        for op, dstr, _hv in self._sig:
            if op in _MOMENT_OPS:
                slots += [np.int64(0), np.float64(0.0), np.float64(0.0)]
            elif op == "sum":
                slots.append(np.zeros(
                    (), _sum_acc_dtype(np.dtype(dstr)))[()])
            elif op == "sumnull":
                slots += [np.zeros((), _sum_acc_dtype(np.dtype(dstr)))[()],
                          np.int64(0)]
            elif op in ("count", "size"):
                slots.append(np.int64(0))
            elif op in ("min", "max"):
                slots += [_minmax_identity(np.dtype(dstr), op),
                          np.int64(0)]
            elif op == "prod":
                slots.append(np.float64(1.0))
        return tuple(jnp.asarray(s) for s in slots)

    def push(self, batch: Table) -> None:
        if self._template is None:
            self._template = batch
            self._sig = tuple(
                (op, str(batch.column(col).data.dtype),
                 batch.column(col).valid is not None)
                for col, op, _ in self.aggs)
        if self._carry is None:
            self._carry = self._init_carry()
        arrays = tuple((batch.column(col).data, batch.column(col).valid)
                       for col, _, _ in self.aggs)
        step = _build_reduce_step(self._sig, batch.capacity, self._donate)
        old = self._carry
        with tracing.event("stream_reduce"):
            self._carry = step(old, arrays, jnp.asarray(batch.nrows))
        self._nbatches += 1
        if self._donate and self.donation_verified is None:
            self.donation_verified = verify_carry_donation(old)

    def finish(self) -> Dict:
        from bodo_tpu.relational import _reduce_scalar
        if self._carry is None:
            host: List = []
        else:
            host = [np.asarray(x) for x in tracing.host_read(
                "stream.carry", self._carry)]  # dispatch-boundary
        res = {}
        ci = 0
        for i, (col, op, oname) in enumerate(self.aggs):
            src_dt = (self._template.column(col).dtype
                      if self._template is not None else None)
            if op in _MOMENT_OPS:
                if not host:
                    res[oname] = np.nan
                    continue
                n = int(host[ci])
                s, m2 = float(host[ci + 1]), float(host[ci + 2])
                ci += 3
                if n == 0:
                    res[oname] = np.nan
                elif op == "mean":
                    res[oname] = _reduce_scalar(s / n, op, src_dt, n)
                else:
                    ddof = 0 if op.endswith("0") else 1
                    if n > ddof:
                        v = max(m2 / (n - ddof), 0.0)
                        v = float(np.sqrt(v)) if op.startswith("std") else v
                        res[oname] = _reduce_scalar(v, op, src_dt, n)
                    else:
                        res[oname] = np.nan
            elif op == "sum":
                res[oname] = (_reduce_scalar(host[ci], op, src_dt, None)
                              if host else np.nan)
                ci += 1
            elif op == "sumnull":
                if host and int(host[ci + 1]):
                    res[oname] = _reduce_scalar(host[ci], op, src_dt,
                                                int(host[ci + 1]))
                else:
                    res[oname] = np.nan
                ci += 2
            elif op in ("count", "size"):
                res[oname] = int(host[ci]) if host else 0
                ci += 1
            elif op in ("min", "max"):
                if host and int(host[ci + 1]):
                    res[oname] = _reduce_scalar(host[ci], op, src_dt,
                                                int(host[ci + 1]))
                else:
                    res[oname] = np.nan
                ci += 2
            elif op == "prod":
                res[oname] = (_reduce_scalar(host[ci], op, src_dt, None)
                              if host else 1.0)
                ci += 1
        return res


class _CarryView:
    """Duck-typed Table over a flat carry tuple, so the observatory's
    `verify_donation` (which walks `.columns[*].data/.valid`) can check
    a streamed carry's buffers were consumed by a donated dispatch."""

    class _Col:
        __slots__ = ("data", "valid")

        def __init__(self, data):
            self.data, self.valid = data, None

    def __init__(self, carry: Sequence):
        self.columns = {f"__c{i}": self._Col(a)
                        for i, a in enumerate(carry)}


def verify_carry_donation(carry: Sequence) -> bool:
    """After a donated streaming step, prove the previous carry's device
    buffers were actually consumed (not silently copied) via the
    observatory ledger. Returns the verdict; also feeds the
    donated-dispatch verification counters."""
    from bodo_tpu.runtime import xla_observatory as xobs
    return xobs.verify_donation(_CarryView(carry))


class SortAccumulator:
    """Streaming sort input: batches park in the native host pool
    (spillable, arbitrated by the operator comptroller) during
    accumulation; the sort itself runs on the restored whole table
    (device peak during accumulate is O(batch))."""

    def __init__(self, by, ascending, na_last: bool):
        from bodo_tpu.runtime.comptroller import default_comptroller
        from bodo_tpu.runtime.memory_governor import governor
        self._comp = default_comptroller()
        self._op = self._comp.register("stream_sort")
        self._grant = governor().admit("stream_sort")
        self.by, self.ascending, self.na_last = by, ascending, na_last
        self.parts: List = []

    def push(self, batch: Table) -> None:
        if batch.nrows:
            from bodo_tpu.runtime.memory_governor import \
                table_device_bytes
            part = _with_capacity(batch, _bucket_cap(max(batch.nrows, 1)))
            self.parts.append(self._comp.park(self._op, part))
            self._grant.record_spill(table_device_bytes(part))

    def finish(self) -> Table:
        assert self.parts, "empty stream — caller must fall back"
        tables = [p.restore() for p in self.parts]
        self.parts = []
        self._comp.unregister(self._op)
        self._grant.release()
        t = R.concat_tables(tables) if len(tables) > 1 else tables[0]
        return R.sort_table(t, self.by, self.ascending, self.na_last)

    def close(self) -> None:
        """Abandon without sorting (empty-stream fallback): free parked
        buffers and drop the comptroller registration."""
        for p in self.parts:
            p.free()
        self.parts = []
        self._comp.unregister(self._op)
        self._grant.release()


class StreamJoin:
    """Per-batch probe against a fully-built (offloaded) build side —
    the reference's streaming hash join with the build table parked in
    the buffer pool (bodo/libs/streaming/_join.cpp HashJoinState),
    accounted to this operator by the comptroller."""

    def __init__(self, build: Table, left_on, right_on, how, suffixes,
                 null_equal: bool = True):
        from bodo_tpu.runtime.comptroller import default_comptroller
        from bodo_tpu.runtime.memory_governor import (governor,
                                                      table_device_bytes)
        self.left_on, self.right_on = left_on, right_on
        self.how, self.suffixes = how, suffixes
        self.null_equal = null_equal
        self._comp = default_comptroller()
        self._op = self._comp.register("stream_join_build")
        if build.distribution != REP:
            # count-only comm row naming the streaming stage boundary;
            # the transfer wall/bytes land on the nested Table.gather
            # span, so no wall here (it would double-count in totals)
            from bodo_tpu.parallel import comm
            comm.record("stream_build_gather",
                        bytes_in=comm.table_bytes(build))
        b = build.gather() if build.distribution != REP else build
        self._grant = governor().admit("stream_join_build",
                                       want=table_device_bytes(b))
        self._off = self._comp.park(self._op, b)
        self._grant.record_spill(table_device_bytes(b))
        self._build: Optional[Table] = None

    def __call__(self, batch: Table) -> Table:
        if self._build is None:
            self._build = self._off.restore()
            self._comp.unregister(self._op)
            self._grant.release()
            # warm the device-resident build table once on restore so
            # every probe batch (including the first) skips the build
            # and its host dup-check sync (plan/fusion_join LRU)
            from bodo_tpu.plan import fusion_join
            fusion_join.prime_build(self._build, self.right_on,
                                    self.null_equal)
        out = R.join_tables(batch, self._build, self.left_on, self.right_on,
                            self.how, self.suffixes,
                            null_equal=self.null_equal)
        return _with_capacity(out, _bucket_cap(max(out.nrows, 1)))

    def close(self) -> None:
        """Release the parked build side if it was never probed (empty
        probe stream) — otherwise the comptroller would account a dead
        build table forever."""
        if self._build is None and not self._off._closed:
            self._off.free()
            self._comp.unregister(self._op)
            self._grant.release()


# ---------------------------------------------------------------------------
# plan → stream compilation
# ---------------------------------------------------------------------------

def _build_stream(node: L.Node) -> Optional[Iterator[Table]]:
    """Compile a plan subtree into a batch iterator, or None if any node
    is not streamable."""
    batch_rows = config.streaming_batch_size

    # scan sources run behind a Prefetcher: batch k+1 decodes on a host
    # thread while batch k runs on the device (runtime/io_pool.py). The
    # wrapper is lazy + self-closing, so a stream that try_stream_execute
    # builds and then abandons costs no thread.
    from bodo_tpu.runtime.io_pool import prefetched
    if isinstance(node, L.ReadParquet):
        return prefetched(
            parquet_batches(node.path, node.columns, batch_rows),
            label="parquet")
    if isinstance(node, L.ReadCsv):
        return prefetched(
            csv_batches(node.path, node.columns, node.parse_dates,
                        batch_rows),
            label="csv")
    if isinstance(node, L.FromPandas):
        if node.table.distribution != REP:
            return None
        return table_batches(node.table, batch_rows)
    if isinstance(node, (L.Filter, L.Projection)):
        # whole-stage fusion: compile a maximal filter/project chain
        # into ONE jitted per-batch program (single compaction at chain
        # exit) instead of one dispatch per stage per batch
        from bodo_tpu.plan import fusion
        chain = fusion.stream_chain(node)
        if chain is not None:
            steps, src = chain
            inner = _build_stream(src)
            if inner is None:
                return None
            out = fusion.fused_batches(steps, inner)
            if any(isinstance(s, L.Filter) for s in steps):
                from bodo_tpu.plan import adaptive
                out = adaptive.coalesce_batches(out, sharded=False)
            return out
    if isinstance(node, L.Filter):
        inner = _build_stream(node.child)
        if inner is None:
            return None
        pred = node.predicate

        def gen_filter(src):
            for b in src:
                yield R.filter_table(b, pred)
        # a selective filter leaves a tail of near-empty batches; merge
        # them back up to a useful fill before the next per-batch kernel
        from bodo_tpu.plan import adaptive
        return adaptive.coalesce_batches(gen_filter(inner), sharded=False)
    if isinstance(node, L.Projection):
        inner = _build_stream(node.child)
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
            # right/outer emit unmatched BUILD rows: probing per batch
            # would duplicate them once per batch; cross would need the
            # probe-major order across batches — whole-table path instead
            return None
        inner = _build_stream(node.left)
        if inner is None:
            return None
        from bodo_tpu.runtime.pool import has_native_pool
        if not has_native_pool():
            # no C++ toolchain: whole-table fallback is correct, just
            # not memory-bounded
            log(1, "stream join disabled: native host pool unavailable")
            return None
        from bodo_tpu.plan import physical
        build = physical._exec(node.right)
        lo, ro = node.left_on, node.right_on
        how, suf, ne = node.how, node.suffixes, node.null_equal

        def gen_join(src):
            # the build side parks in the pool only once the generator
            # actually RUNS: a caller that abandons a never-started
            # generator skips `finally` blocks entirely (PEP 342), so an
            # eager park here would leak in the comptroller
            join = None
            try:
                for b in src:
                    if join is None:
                        join = StreamJoin(build, lo, ro, how, suf, ne)
                    yield join(b)
            finally:
                if join is not None:
                    join.close()  # releases the build if never probed
        return gen_join(inner)
    return None


def stream_to_parquet(node: L.Node, path: str) -> bool:
    """Stream an (already optimized) plan straight into a parquet file,
    one row group per batch — end-to-end bounded device memory for
    scan→filter→project→write shapes (reference:
    bodo/io/stream_parquet_write.py). Returns False when the plan isn't a
    streamable chain (caller materializes). Caller gates on
    config.stream_exec."""
    if mesh_mod.num_shards() > 1:
        return False
    # writing over one of the plan's own sources would truncate it while
    # the lazy reader is mid-file — materialize instead
    target = os.path.abspath(path)

    def reads_target(n: L.Node) -> bool:
        if isinstance(n, (L.ReadParquet, L.ReadCsv)):
            src_p = os.path.abspath(n.path)
            if src_p == target or src_p.startswith(target + os.sep) or \
                    target.startswith(src_p + os.sep):
                return True
        return any(reads_target(c) for c in n.children)

    if reads_target(node):
        return False
    src = _build_stream(node)
    if src is None:
        return False
    from bodo_tpu.io.parquet import StreamingParquetWriter
    n = 0
    with StreamingParquetWriter(path) as w:
        for b in src:
            w.push(b)
            n += 1
    if n == 0:
        return False  # empty stream: no schema to write — materialize
    log(1, f"streaming parquet write: {n} batches -> {path}")
    return True


def try_stream_execute(node: L.Node) -> Optional[Table]:
    """Execute a plan with the streaming batch executor when its shape
    supports it; None → caller falls back to whole-table execution."""
    if not config.stream_exec:
        return None
    from bodo_tpu.plan import adaptive
    from bodo_tpu.runtime.resilience import maybe_inject
    maybe_inject("stage.boundary")
    if mesh_mod.num_shards() > 1:
        from bodo_tpu.plan.streaming_sharded import \
            try_stream_execute_sharded
        return try_stream_execute_sharded(node)

    if isinstance(node, L.Aggregate):
        from bodo_tpu.table import dtypes as dt_
        if any(dt_.is_decimal(node.child.schema[c])
               for c, _, _ in node.aggs):
            return None  # streaming agg state isn't decimal-aware yet
        src = _build_stream(node.child)
        if src is None:
            return None
        try:
            acc = GroupbyAccumulator(node.keys, node.aggs)
        except NotImplementedError:
            try:
                # non-decomposable aggs: mixed streaming strategies
                # (distinct-pairs nunique, spillable ACC-mode rowstore)
                acc = MixedGroupbyStream(node.keys, node.aggs)
            except NotImplementedError:
                return None
        nb = 0
        for b in src:
            adaptive.observe_batch(b)
            acc.push(b)
            nb += 1
            _note_batch()
        if isinstance(acc, GroupbyAccumulator):
            if acc._template is None:
                return None  # empty stream: no schema — fall back
            log(1, f"streaming groupby: {nb} batches, "
                   f"{acc.n_state} groups")
            return acc.finish()
        if acc.dec._template is None:
            acc.close()
            return None
        log(1, f"streaming mixed groupby: {nb} batches")
        return acc.finish()

    if isinstance(node, L.Reduce):
        src = _build_stream(node.child)
        if src is None:
            return None
        try:
            acc = ReduceAccumulator(node.aggs)
        except NotImplementedError:
            return None
        for b in src:
            adaptive.observe_batch(b)
            acc.push(b)
            _note_batch()
        scalars = acc.finish()
        import pandas as pd
        return Table.from_pandas(
            pd.DataFrame({k: [v] for k, v in scalars.items()}))

    if isinstance(node, L.Sort):
        src = _build_stream(node.child)
        if src is None:
            return None
        try:
            acc = SortAccumulator(node.by, node.ascending, node.na_last)
        except RuntimeError as e:
            # native host pool unavailable: whole-table fallback
            log(1, f"stream sort disabled, falling back: {e}")
            return None
        for b in src:
            adaptive.observe_batch(b)
            acc.push(b)
            _note_batch()
        if not acc.parts:
            acc.close()
            return None  # empty stream: fall back (handles the 0-row case)
        return acc.finish()

    return None
