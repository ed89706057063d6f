"""Hash/range shuffle over the mesh — the engine's repartitioning core.

TPU-native replacement for the reference's MPI alltoallv shuffle
(bodo/libs/_shuffle.cpp `shuffle_table`, bodo/libs/streaming/_shuffle.h:777
`IncrementalShuffleState`). The variable-count alltoallv becomes a
fixed-capacity `lax.all_to_all`: each shard packs its rows into S buckets
of static capacity C (destination = hash or range of the key), exchanges
the buckets over ICI, then compacts received rows using exchanged
per-source counts. Overflowing a bucket sets a flag the host checks and
retries with a larger C (the analogue of the reference's partition
re-splitting on memory pressure, streaming/_join.h:267).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from bodo_tpu.config import config
from bodo_tpu.ops import kernels as K
from bodo_tpu.ops.groupby import (COMBINE_OF, DECOMPOSE, HASH_OPS,
                                  _var_from_m2, groupby_local,
                                  groupby_local_hashed_static,
                                  result_dtype)
from bodo_tpu.ops.hashing import dest_shard, hash_columns
from bodo_tpu.ops import pallas_kernels as PK
from bodo_tpu.ops.sort_encoding import stable_argsort
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan.fusion import fusion_stage
from bodo_tpu.utils.kernel_cache import cached_builder, named_jit
from bodo_tpu.utils.tracing import host_read


# ---------------------------------------------------------------------------
# bucket pack / unpack (runs per shard, inside shard_map)
# ---------------------------------------------------------------------------
# These bodies trace into compiled sharded programs (and may be inlined
# into fused whole-stage pipelines): @fusion_stage puts them under the
# shardcheck fusion-host-call lint — no host sync is legal inside.

@fusion_stage
def bucket_rows(dest, arrays: Sequence, count, num_shards: int,
                bucket_cap: int):
    """Pack rows into per-destination buckets of capacity `bucket_cap`.

    dest: int32 [cap] destination shard per row (padding rows ignored).
    Returns (packed arrays [S*C,...], send_counts [S], overflow flag).
    """
    cap = dest.shape[0]
    padmask = K.row_mask(count, cap)
    d = jnp.where(padmask, dest, num_shards).astype(jnp.int32)
    live = padmask & (d < num_shards)
    # bucket partition scatter: the Pallas partition_rank kernel derives
    # every row's stable in-bucket rank AND the per-bucket histogram in
    # one grid pass (triangular-matmul prefix + VMEM running base), so
    # the XLA stable sort below never runs when the gate is open
    res = PK.partition_rank(d, live, num_shards)
    if res is not None:
        rank, counts = res
        ok = live & (rank >= 0) & (rank < bucket_cap)
        overflow = jnp.any(live & (rank >= bucket_cap))
        scatter_idx = jnp.where(ok, d * bucket_cap + rank,
                                num_shards * bucket_cap)
        packed = []
        for a in arrays:
            if a is None:
                packed.append(None)
                continue
            z = jnp.zeros((num_shards * bucket_cap,) + a.shape[1:],
                          dtype=a.dtype)
            packed.append(z.at[scatter_idx].set(a, mode="drop"))
        send_counts = jnp.minimum(counts.astype(jnp.int64), bucket_cap)
        return packed, send_counts, overflow
    # stable sort rows by destination
    perm = stable_argsort([(d.astype(jnp.uint32), 32)])
    d_s = d[perm]
    pos = jnp.arange(cap)
    is_new = (d_s != jnp.roll(d_s, 1)) | (pos == 0)
    group_start = lax.cummax(jnp.where(is_new, pos, 0))
    idx_in = pos - group_start
    ok = (d_s < num_shards) & (idx_in < bucket_cap)
    overflow = jnp.any((d_s < num_shards) & (idx_in >= bucket_cap))
    scatter_idx = jnp.where(ok, d_s * bucket_cap + idx_in,
                            num_shards * bucket_cap)
    packed = []
    for a in arrays:
        if a is None:
            packed.append(None)
            continue
        z = jnp.zeros((num_shards * bucket_cap,) + a.shape[1:], dtype=a.dtype)
        packed.append(z.at[scatter_idx].set(a[perm], mode="drop"))
    # bucket-partition counting: Pallas one-hot MXU histogram when the
    # kernel gate is open (XLA lowers the segment_sum to a scatter-add
    # that serializes on the VPU); plain segment_sum elsewhere
    send_counts = PK.bucket_counts(
        jnp.minimum(d, num_shards), padmask,
        num_shards + 1)[:num_shards].astype(jnp.int64)
    send_counts = jnp.minimum(send_counts, bucket_cap)
    return packed, send_counts, overflow


@fusion_stage
def exchange_and_compact(packed: Sequence, send_counts, num_shards: int,
                         bucket_cap: int, axis: Optional[str] = None):
    """all_to_all the packed buckets + counts, then compact received rows.

    Returns (arrays [S*C,...] compacted to front, recv_count scalar).
    """
    recvd = [None if a is None else C.all_to_all_rows(a, axis) for a in packed]
    rcounts = C.all_to_all_rows(send_counts, axis)  # [S]: rows from each src
    total = num_shards * bucket_cap
    slot = jnp.arange(total)
    mask = (slot % bucket_cap) < rcounts[slot // bucket_cap]
    out, cnt = K.compact(mask, tuple(recvd))
    return list(out), cnt


@fusion_stage
def shuffle_rows(dest, arrays: Sequence, count, num_shards: int,
                 bucket_cap: int, axis: Optional[str] = None):
    """Full shuffle: bucket → all_to_all → compact. The `shuffle_table`
    analogue (reference bodo/libs/_shuffle.h:41)."""
    packed, send_counts, ovf = bucket_rows(dest, arrays, count, num_shards,
                                           bucket_cap)
    out, cnt = exchange_and_compact(packed, send_counts, num_shards,
                                    bucket_cap, axis)
    return out, cnt, ovf


# ---------------------------------------------------------------------------
# distributed groupby: partial-agg → hash shuffle → combine → finalize
# ---------------------------------------------------------------------------

def _plan_decomposition(specs: Tuple[str, ...]):
    """Map final agg specs to (partial specs, combine specs, layout).

    layout[i] = (offset, n) slice of partial columns feeding final spec i.
    """
    partial_specs: List[str] = []
    combine_specs: List[str] = []
    layout = []
    for op in specs:
        if op not in DECOMPOSE:
            raise NotImplementedError(
                f"agg '{op}' is not decomposable for the distributed "
                f"two-phase groupby; execute it via gather + local groupby "
                f"(supported distributed aggs: {sorted(DECOMPOSE)})")
        parts = DECOMPOSE[op]
        layout.append((len(partial_specs), len(parts)))
        partial_specs.extend(parts)
        combine_specs.extend(COMBINE_OF[p] for p in parts)
    return tuple(partial_specs), tuple(combine_specs), tuple(layout)


def _finalize(op: str, cols, orig_dtype):
    """Derive the final column from combined partial columns."""
    if op == "mean":
        (s, _), (cnt, _) = cols
        rdt = result_dtype("mean", orig_dtype)
        m = s.astype(rdt) / jnp.maximum(cnt, 1).astype(rdt)
        return jnp.where(cnt > 0, m, jnp.nan), None
    if op in ("var", "std", "var0", "std0"):
        # combined partials are (n, Σx, M2) — M2 already merged exactly by
        # the chan_m2 composite combine (see ops/groupby.py groupby_local)
        (cnt, _), (_s, _), (m2, _) = cols
        rdt = result_dtype(op, orig_dtype)
        ddof = 0 if op.endswith("0") else 1
        out = _var_from_m2(m2, cnt, ddof=ddof)
        return (jnp.sqrt(out) if op.startswith("std")
                else out).astype(rdt), None
    if op == "skew":
        from bodo_tpu.ops.groupby import _skew_from_moments
        (cnt, _), _s, (m2, _), (m3, _) = cols
        return _skew_from_moments(cnt, m2, m3), None
    if op == "kurt":
        from bodo_tpu.ops.groupby import _kurt_from_moments
        (cnt, _), _s, (m2, _), _m3, (m4, _) = cols
        return _kurt_from_moments(cnt, m2, m4), None
    return cols[0]


@cached_builder("shuffle")
def _build_groupby_partial(mesh_key, num_keys: int, specs: Tuple[str, ...],
                           method: str = "sort"):
    """Stage 1: per-shard partial aggregation (shrinks data before the
    wire — the reference's local-combine motivation). method='hash'
    replaces the per-shard row sort with the scatter-claim hash kernel
    (ops/hashtable.py); its traced `unresolved` flag is OR-visible to
    the host, which falls back to 'sort' on pathological keys."""
    mesh = _MESHES[mesh_key]
    axis = config.data_axis
    partial_specs, _, _ = _plan_decomposition(specs)

    def body(arrays, counts):
        count = counts[0]
        cap = arrays[0][0].shape[0]
        keys = arrays[:num_keys]
        values = arrays[num_keys:]
        p_inputs = tuple(keys) + tuple(
            values[i] for i, op in enumerate(specs)
            for _ in DECOMPOSE[op])
        if method == "hash":
            pk, pv, ng, unres = groupby_local_hashed_static(
                p_inputs, count, partial_specs, cap, num_keys)
        else:
            pk, pv, ng = groupby_local(p_inputs, count, partial_specs,
                                       cap, num_keys)
            unres = jnp.zeros((), bool)
        return (pk, pv), ng[None], unres[None]

    shd = C.smap(body, in_specs=(P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P(axis)), mesh=mesh)
    return named_jit("groupby_sharded_partial", shd)


def shuffle_partials(pk, pv, num_keys: int, S: int, bucket_cap: int,
                     ng, axis):
    """Hash-shuffle packed groupby partials to their owner shard.

    pk/pv: key / partial-value (data, valid) pairs packed at the front
    (ng live rows). Validity masks ride the wire as extra slots next to
    their data column; keys come back maskless (group keys are
    canonical). Returns (recv_keys, recv_vals, recv_count, overflow) —
    the one shared layout convention for every shuffle-partials caller
    (whole-table two-phase groupby and the streaming accumulator)."""
    h = hash_columns(pk)
    dest = dest_shard(h, S)
    flat: List = [d for d, _ in pk]
    has_valid: List[bool] = []
    for d, v in pv:
        flat.append(d)
        if v is not None:
            has_valid.append(True)
            flat.append(v)
        else:
            has_valid.append(False)
    out, cnt, ovf = shuffle_rows(dest, flat, ng, S, bucket_cap, axis)
    rk = tuple((out[i], None) for i in range(num_keys))
    rv = []
    j = num_keys
    for hv in has_valid:
        if hv:
            rv.append((out[j], out[j + 1].astype(bool)))
            j += 2
        else:
            rv.append((out[j], None))
            j += 1
    return rk, tuple(rv), cnt, ovf


@cached_builder("shuffle")
def _build_groupby_combine(mesh_key, num_keys: int, specs: Tuple[str, ...],
                           value_dtypes: Tuple, bucket_cap: int,
                           final_cap: int):
    """Stage 2: hash-shuffle partial rows at a tight bucket capacity, then
    combine + finalize. The host sizes bucket_cap from stage-1 counts and
    retries on overflow (analogue of partition re-splitting)."""
    mesh = _MESHES[mesh_key]
    axis = config.data_axis
    S = mesh.shape[axis]
    _, combine_specs, layout = _plan_decomposition(specs)

    def body(partials, ngs):
        pk, pv = partials
        ng = ngs[0]
        rk, rv, cnt2, ovf = shuffle_partials(pk, pv, num_keys, S,
                                             bucket_cap, ng, axis)
        fk, fv, ng2 = groupby_local(rk + rv, cnt2, combine_specs,
                                    final_cap, num_keys)
        finals = []
        for i, op in enumerate(specs):
            off, n = layout[i]
            finals.append(_finalize(op, fv[off:off + n],
                                    jnp.dtype(value_dtypes[i])))
        return (fk, tuple(finals)), ng2[None], ovf[None]

    shd = C.smap(body, in_specs=(P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P(axis)), mesh=mesh)
    return named_jit("groupby_sharded_combine", shd)


_MESHES = {}


def _mesh_key(mesh):
    k = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
    _MESHES[k] = mesh
    return k


def groupby_sharded(arrays, counts, num_keys: int, specs: Tuple[str, ...],
                    bucket_cap=None, final_cap=None, mesh=None):
    """Distributed two-phase groupby over row-sharded arrays.

    arrays: tuple of (data, valid) with data sharded [S*cap]; counts [S].
    Returns ((out_keys, out_finals), n_groups [S], overflow [S]).

    Host-visible staging: after the partial stage the host reads the
    per-shard partial counts and sizes the shuffle buckets tightly
    (expected rows per (src,dest) pair × skew headroom), growing them on
    overflow up to the always-safe bound (= max partial count).

    This is a HOST-level entry (device_get between stages), so it owns
    a query-tagged tracing span; the inner shuffle_rows/shuffle_partials
    run under jit tracing and must stay side-effect free.
    """
    from bodo_tpu.utils import tracing
    with tracing.event("groupby_sharded", specs=list(specs)):
        return _groupby_sharded_impl(arrays, counts, num_keys, specs,
                                     bucket_cap, final_cap, mesh)


def _groupby_sharded_impl(arrays, counts, num_keys: int,
                          specs: Tuple[str, ...], bucket_cap=None,
                          final_cap=None, mesh=None):
    from bodo_tpu.table.table import round_capacity
    m = mesh or mesh_mod.get_mesh()
    S = m.shape[config.data_axis]
    mk = _mesh_key(m)
    value_dtypes = tuple(str(arrays[num_keys + i][0].dtype)
                         for i in range(len(specs)))

    method = "sort"
    if config.hash_groupby:
        try:
            partial_specs, _, _ = _plan_decomposition(specs)
            if all(p in HASH_OPS for p in partial_specs):
                method = "hash"
        except NotImplementedError:
            pass
    while True:
        partials, ngs, unres = _build_groupby_partial(
            mk, num_keys, specs, method)(tuple(arrays), counts)
        if method == "hash" and \
                np.asarray(host_read("group_unresolved", unres)).any():
            method = "sort"  # pathological keys on some shard
            continue
        break
    png = np.asarray(host_read("group_count", ngs)).reshape(-1)
    max_png = int(png.max()) if len(png) else 0
    safe_cap = round_capacity(max(max_png, 1))
    if bucket_cap is None:
        bucket_cap = round_capacity(
            int(config.shuffle_skew_factor * max(max_png, 1) / S) + 64)
        bucket_cap = min(bucket_cap, safe_cap)
    while True:
        fcap = final_cap if final_cap is not None else S * bucket_cap
        fn = _build_groupby_combine(mk, num_keys, specs, value_dtypes,
                                    bucket_cap, fcap)
        out, ng2, ovf = fn(partials, ngs)
        if not np.asarray(host_read("shuffle_overflow", ovf)).any():
            return out, ng2, ovf
        if bucket_cap >= safe_cap:
            raise RuntimeError("groupby shuffle overflow at safe capacity")
        bucket_cap = min(bucket_cap * 4, safe_cap)
