"""Collective primitives over the device mesh.

This module mirrors the surface of the reference's distributed API
(reference: bodo/libs/distributed_api.py — get_rank:?, dist_reduce:510,
dist_exscan:664, gatherv:713, allgatherv:1022, scatterv:1299, bcast:2578;
C++ side bodo/libs/_distributed.h:72 `BODO_ReduceOps`) but implemented with
jax.lax collectives that XLA lowers onto ICI/DCN:

    MPI_Allreduce   -> lax.psum / pmax / pmin
    MPI_Exscan      -> all_gather + masked cumsum (exscan)
    MPI_Allgatherv  -> lax.all_gather (fixed-capacity shards + row counts)
    MPI_Alltoallv   -> lax.all_to_all (fixed-capacity buckets, `tiled=True`)
    isend/irecv     -> lax.ppermute ring shifts (halo exchange)

Functions in the "axis context" section must be called inside
`shard_map`/`pjit` bodies where the mesh axis is bound; host-level
gather/scatter helpers live at the bottom.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from bodo_tpu.config import config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.runtime.resilience import maybe_inject as _inject
from bodo_tpu.utils.tracing import host_read


# --------------------------------------------------------------------------
# Axis-context collectives (use inside shard_map bodies)
# --------------------------------------------------------------------------
# NOTE on fault injection: the `collective` point here fires at TRACE
# time (these run inside shard_map/jit bodies, and compiled kernels are
# cached) — it arms chaos for fresh compilations. The per-call host-level
# `collective` point lives at the distributed-op dispatchers in
# relational.py, which is what stage-degradation tests use. The
# shardcheck trace-time-side-effect lint flags exactly this pattern;
# the inline suppressions below mark it as the one intentional case.

axis_size = lax.axis_size


def rank(axis: Optional[str] = None):
    """This shard's index along the data axis (MPI_Comm_rank analogue)."""
    return lax.axis_index(axis or config.data_axis)


def size(axis: Optional[str] = None) -> int:
    """Static number of shards along the data axis (MPI_Comm_size analogue)."""
    return axis_size(axis or config.data_axis)


def dist_sum(x, axis: Optional[str] = None):
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    return lax.psum(x, axis or config.data_axis)


def dist_max(x, axis: Optional[str] = None):
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    return lax.pmax(x, axis or config.data_axis)


def dist_min(x, axis: Optional[str] = None):
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    return lax.pmin(x, axis or config.data_axis)


def dist_exscan_sum(x, axis: Optional[str] = None):
    """Exclusive prefix sum over shards (MPI_Exscan analogue; used for
    1D_Var offset bookkeeping and dist_cumsum — reference
    bodo/libs/distributed_api.py:664, :2205)."""
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    ax = axis or config.data_axis
    n = axis_size(ax)
    gathered = lax.all_gather(x, ax)            # [n, ...]
    idx = lax.axis_index(ax)
    mask = (jnp.arange(n) < idx).astype(gathered.dtype)
    mask = mask.reshape((n,) + (1,) * (gathered.ndim - 1))
    return jnp.sum(gathered * mask, axis=0)


def all_gather_rows(x, axis: Optional[str] = None):
    """Concatenate each shard's rows in rank order: [cap,...] -> [S*cap,...]
    (MPI_Allgatherv analogue; padding travels with the shard and is
    resolved by the caller via per-shard row counts)."""
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    ax = axis or config.data_axis
    return lax.all_gather(x, ax, tiled=True)


def all_to_all_rows(x, axis: Optional[str] = None):
    """Fixed-capacity all-to-all: x has shape [S*C, ...]; contiguous block
    i of C rows is sent to shard i; result is the S received blocks
    concatenated in rank order. This is the alltoallv of the reference's
    shuffle (bodo/libs/_shuffle.h:41, streaming/_shuffle.h:777) with
    capacity-padded buckets instead of variable counts."""
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    ax = axis or config.data_axis
    return lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=True)


def ring_shift(x, shift: int = 1, axis: Optional[str] = None):
    """Send local block to rank+shift (mod S): the neighbor-exchange used
    for rolling-window halos (reference bodo/hiframes/rolling.py,
    bodo/libs/parallel_ops.py) — lax.ppermute over the ring."""
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    ax = axis or config.data_axis
    n = axis_size(ax)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, ax, perm)


def bcast_from(x, root: int = 0, axis: Optional[str] = None):
    """Broadcast shard `root`'s block to all shards (MPI_Bcast analogue,
    reference bodo/libs/distributed_api.py:2578)."""
    _inject("collective")  # shardcheck: ignore[trace-time-side-effect]
    ax = axis or config.data_axis
    gathered = lax.all_gather(x, ax)
    return gathered[root]


# --------------------------------------------------------------------------
# Host-level distribution helpers (outside jit)
# --------------------------------------------------------------------------

def shard_host_array(arr: np.ndarray, capacity_per_shard: Optional[int] = None):
    """Scatter a host array into a row-sharded device array
    (MPI_Scatterv analogue, reference distributed_api.py:1299).

    Each shard receives an equal padded chunk; returns
    (device_array [S*cap_per_shard], per-shard row counts [S]).
    """
    from bodo_tpu.parallel import comm
    _inject("device_put")
    m = mesh_mod.get_mesh()
    s = mesh_mod.num_shards(m)
    n = arr.shape[0]
    base = -(-n // s) if n else 0
    cap = capacity_per_shard if capacity_per_shard is not None else _round_cap(base)
    counts = np.array(
        [max(0, min(cap, n - i * cap)) for i in range(s)], dtype=np.int64
    )
    # NOTE: with cap >= ceil(n/s) every row lands in some shard
    if counts.sum() != n:
        # capacity too small for equal chunking; grow
        cap = _round_cap(-(-n // s))
        counts = np.array(
            [max(0, min(cap, n - i * cap)) for i in range(s)], dtype=np.int64
        )
    padded_shape = (s * cap,) + arr.shape[1:]
    padded = np.zeros(padded_shape, dtype=arr.dtype)
    if n:
        padded[: min(n, s * cap)] = arr[: s * cap]
    with comm.collective_span("scatter_host",
                              bytes_in=int(arr.nbytes)) as sp:
        dev = jax.device_put(padded, NamedSharding(m, P(config.data_axis)))
        sp["bytes_out"] = int(padded.nbytes)
    return dev, counts


def gather_host_rows(dev_arr, counts: np.ndarray) -> np.ndarray:
    """Gather a row-sharded device array back to a host array, trimming
    per-shard padding (MPI_Gatherv analogue, reference
    distributed_api.py:713)."""
    from bodo_tpu.parallel import comm
    s = len(counts)
    with comm.collective_span(
            "gather_host",
            bytes_in=int(getattr(dev_arr, "nbytes", 0))) as sp:
        host = np.asarray(host_read("gather", dev_arr))
        cap = host.shape[0] // s
        pieces = [host[i * cap: i * cap + int(counts[i])]
                  for i in range(s)]
        out = np.concatenate(pieces, axis=0) if pieces else host[:0]
        sp["bytes_out"] = int(out.nbytes)
    return out


def _round_cap(n: int) -> int:
    from bodo_tpu.table.table import round_capacity
    return round_capacity(n)


# --------------------------------------------------------------------------
# shard_map convenience wrapper
# --------------------------------------------------------------------------

def smap(fn, in_specs, out_specs, mesh=None):
    """shard_map over the active mesh with the data axis bound."""
    m = mesh or mesh_mod.get_mesh()
    return shard_map(fn, mesh=m, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


ROW = None  # placeholder; use P(config.data_axis) / P() at call sites
