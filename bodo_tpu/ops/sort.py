"""Sort kernels: local multi-key sort and distributed sample sort.

TPU-native replacement for the reference's external-merge sort and
sample-based range partitioning (bodo/libs/_array_operations.cpp
sort_values paths, bodo/libs/streaming/_sort.cpp, sample bounds via
bodo/libs/distributed_api.py:2114 get_chunk_bounds). The comparator-based
C++ sort becomes `lax.sort` over order-preserving uint64 encodings
(ops/sort_encoding.py); the MPI range shuffle becomes splitter-based
destination assignment + fixed-capacity all_to_all (parallel/shuffle.py).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from bodo_tpu.config import config
from bodo_tpu.ops import kernels as K
from bodo_tpu.ops import sort_encoding as SE
from bodo_tpu.parallel import collectives as C
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.utils.kernel_cache import (bounded_jit, cached_builder,
                                         named_jit)

# oversampling factor for splitter selection (samples per shard = OS * S)
_OVERSAMPLE = 8


def _sort_operands(keys: Sequence[Tuple], ascending: Sequence[bool],
                   na_last: bool, padmask):
    ops: List = []
    for (data, valid), asc in zip(keys, ascending):
        ops.extend(SE.key_operands(data, valid, ascending=asc,
                                   na_last=na_last, padmask=padmask))
    return ops


@bounded_jit(static_argnames=("num_keys", "ascending", "na_last"))
def sort_local(arrays, count, num_keys: int, ascending: Tuple[bool, ...],
               na_last: bool = True):
    """Stable multi-key sort of all columns; first `num_keys` arrays are
    the sort keys. Returns (sorted arrays, perm)."""
    cap = arrays[0][0].shape[0]
    padmask = K.row_mask(count, cap)
    ops = _sort_operands(arrays[:num_keys], ascending, na_last, padmask)
    perm = SE.stable_argsort(ops)
    out = tuple((None if d is None else d[perm],
                 None if v is None else v[perm]) for d, v in arrays)
    return out, perm


def _partition_key(keys: Sequence[Tuple], ascending: Sequence[bool],
                   na_last: bool, padmask):
    """Fold the leading sort key into one uint64 for range partitioning.

    Ties from the fold are harmless: rows with equal partition keys may
    land on adjacent shards, which still yields a globally sorted
    concatenation (every row on shard i sorts <= every row on shard i+1).
    """
    data, valid = keys[0]
    if data.dtype == jnp.float64:
        # the v5e's compiler cannot bitcast a float64 (`encode_value`),
        # and a partition key need only never invert an order: rounding
        # to float32 keeps it, values that round alike go to one shard
        # together, and the final local sort compares the float64 itself
        data = data.astype(jnp.float32)
    enc = SE.encode_value(data, ascending[0])
    null = SE.null_flag(data, valid)
    # layout: [2 bits rank][62 bits value] — rank orders nulls/padding
    rank = jnp.full(data.shape, np.uint64(1), dtype=jnp.uint64)
    if null is not None:
        rank = jnp.where(null, np.uint64(2) if na_last else np.uint64(0),
                         rank)
    pk = (rank << np.uint64(62)) | (enc >> np.uint64(2))
    return jnp.where(padmask, pk, np.uint64(0xFFFFFFFFFFFFFFFF))


@cached_builder("sort")
def _build_sort_sharded(mesh_key, num_arrays: int, num_keys: int,
                        ascending: Tuple[bool, ...], na_last: bool,
                        bucket_cap: int):
    from bodo_tpu.parallel.shuffle import _MESHES, shuffle_rows
    mesh = _MESHES[mesh_key]
    axis = config.data_axis
    S = mesh.shape[axis]

    def body(arrays, counts):
        count = counts[0]
        cap = arrays[0][0].shape[0]
        padmask = K.row_mask(count, cap)
        pk = _partition_key(arrays[:num_keys], ascending, na_last, padmask)

        # 1. sample partition keys at even local quantiles
        k = _OVERSAMPLE * S
        pk_sorted = lax.sort(pk)
        idx = (jnp.arange(k) * jnp.maximum(count, 1)) // k
        samples = pk_sorted[jnp.clip(idx, 0, cap - 1)]
        samples = jnp.where(jnp.arange(k) * jnp.maximum(count, 1) // k < count,
                            samples, np.uint64(0xFFFFFFFFFFFFFFFF))
        all_samples = C.all_gather_rows(samples, axis)          # [S*k]
        svalid = all_samples != np.uint64(0xFFFFFFFFFFFFFFFF)
        s_sorted = lax.sort(jnp.where(svalid, all_samples,
                                      np.uint64(0xFFFFFFFFFFFFFFFF)))
        nvalid = jnp.sum(svalid)
        # splitters: S-1 even quantiles of the valid samples
        spl_idx = (jnp.arange(1, S) * jnp.maximum(nvalid, 1)) // S
        splitters = s_sorted[jnp.clip(spl_idx, 0, S * k - 1)]

        # 2. range shuffle (dest = #splitters < pk): the Pallas radix
        # partition kernel decides uint64 order by 16-bit planes on the
        # VPU; XLA searchsorted when the gate is closed
        from bodo_tpu.ops import pallas_kernels as PK
        dest = PK.range_partition(pk, splitters)
        if dest is None:
            dest = jnp.searchsorted(splitters, pk,
                                    side="right").astype(jnp.int32)
        flat: List = []
        slots = []
        for d, v in arrays:
            flat.append(d)
            if v is not None:
                slots.append(True)
                flat.append(v)
            else:
                slots.append(False)
        out, cnt2, ovf = shuffle_rows(dest, flat, count, S, bucket_cap, axis)
        rebuilt = []
        j = 0
        for has_valid in slots:
            if has_valid:
                rebuilt.append((out[j], out[j + 1].astype(bool)))
                j += 2
            else:
                rebuilt.append((out[j], None))
                j += 1

        # 3. final local sort
        sorted_arrays, _ = sort_local(tuple(rebuilt), cnt2, num_keys,
                                      ascending, na_last)
        return sorted_arrays, cnt2[None], ovf[None]

    shd = C.smap(body, in_specs=(P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P(axis)), mesh=mesh)
    return named_jit("sort_sharded", shd)


def sort_sharded(arrays, counts, num_keys: int, ascending: Tuple[bool, ...],
                 na_last: bool = True, mesh=None):
    """Distributed sample sort of row-sharded columns.

    Globally sorted result: shard i's rows all sort <= shard i+1's rows,
    each shard locally sorted. Splitter-balanced buckets are sized
    optimistically (cap/S × skew headroom) and grown on overflow up to the
    always-safe bound of cap per (src,dest) pair.
    Returns (sorted arrays, new counts [S]).
    """
    import numpy as np

    from bodo_tpu.parallel.shuffle import _mesh_key
    from bodo_tpu.table.table import round_capacity
    m = mesh or mesh_mod.get_mesh()
    S = m.shape[config.data_axis]
    cap = arrays[0][0].shape[0] // S
    bucket_cap = min(round_capacity(
        int(config.shuffle_skew_factor * cap / S) + 64), cap)
    while True:
        fn = _build_sort_sharded(_mesh_key(m), len(arrays), num_keys,
                                 tuple(ascending), na_last, bucket_cap)
        out, cnts, ovf = fn(tuple(arrays), counts)
        if not np.asarray(jax.device_get(ovf)).any():
            return out, cnts
        if bucket_cap >= cap:
            raise RuntimeError("sort shuffle overflow at safe capacity")
        bucket_cap = min(bucket_cap * 4, cap)
