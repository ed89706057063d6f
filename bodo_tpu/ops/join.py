"""Join kernels: sort-based equi-join with exact multi-key matching.

TPU-native replacement for the reference's hash-join family
(bodo/libs/_hash_join.cpp, _join_hashing.cpp, streaming/_join.h:892
HashJoinState). Hash tables don't map well to XLA's static dataflow, so
we use a union-segmentation design instead (SURVEY.md §7 "sort-based
fallback is the safety net", here promoted to the primary):

  1. concatenate probe+build key columns and segment them with the same
     stable sort machinery as groupby — every row gets an exact group id
     (gid); key equality becomes integer gid equality, which also makes
     multi-key joins exact without composite-key bit-packing.
  2. order build rows by gid; per-gid [start, count) ranges come from a
     cumsum. Each probe row matches `count[gid]` build rows.
  3. expansion: output slot j maps back to its (probe, build) pair with
     one searchsorted over the exclusive cumsum of match counts — fully
     static shapes, with an overflow flag the host uses to re-bucket
     (the analogue of the reference's partition re-splitting).

Dynamic output size is handled by the two-call pattern: `join_count`
returns the exact row count, the host picks a padded capacity bucket,
then `join_local` materializes.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bodo_tpu.ops import kernels as K
from bodo_tpu.ops import sort_encoding as SE
from bodo_tpu.utils.kernel_cache import bounded_jit


def _union_gids(probe_keys, build_keys, p_padmask, b_padmask,
                null_equal: bool = False):
    """Segment the union of probe+build keys; returns (gid_p, gid_b).

    Excluded rows get gid == ucap (sentinel, matches nothing because
    build counts are only accumulated for real rows). null_equal=False
    (SQL): null keys are excluded — they never match. null_equal=True
    (pandas merge): nulls form a real group and match other nulls
    (a null in any key position compares equal to a null there)."""
    pcap = probe_keys[0][0].shape[0]
    bcap = build_keys[0][0].shape[0]
    ucap = pcap + bcap
    unionmask = jnp.concatenate([p_padmask, b_padmask])
    operands: List = []
    ukeys = []
    for (pd_, pv), (bd, bv) in zip(probe_keys, build_keys):
        d = jnp.concatenate([pd_, bd.astype(pd_.dtype)])
        if pv is None and bv is None:
            v = None
        else:
            pv_ = pv if pv is not None else jnp.ones(pcap, dtype=bool)
            bv_ = bv if bv is not None else jnp.ones(bcap, dtype=bool)
            v = jnp.concatenate([pv_, bv_])
        ukeys.append((d, v))
        nf = SE.null_flag(d, v)
        if not null_equal:
            if nf is not None:
                unionmask = unionmask & ~nf
            operands.extend(SE.key_operands(d, v, padmask=unionmask))
        elif nf is not None:
            # sort all nulls of this key into one adjacent block with a
            # CONSTANT value encoding (zeroed data) — a mask-null's
            # garbage payload must not scatter equal follow-on keys
            dz = jnp.where(nf, jnp.zeros((), d.dtype), d)
            rank = jnp.where(nf, jnp.uint8(2), jnp.uint8(1))
            rank = jnp.where(unionmask, rank, jnp.uint8(3))
            operands.extend([(rank, 2), SE.encode_field(dz)])
        else:
            operands.extend(SE.key_operands(d, v, padmask=unionmask))
    perm = SE.stable_argsort(operands)
    umask_s = unionmask[perm]
    pos = jnp.arange(ucap)
    diff = jnp.zeros(ucap, dtype=bool).at[0].set(True)
    for d, v in ukeys:
        ks = d[perm]
        if null_equal:
            # canonicalize: all nulls (mask or NaN) compare equal to each
            # other and different from every value (raw NaN != NaN would
            # make each null row its own group)
            nf = SE.null_flag(d, v)
            if nf is not None:
                ns = nf[perm]
                ks = jnp.where(ns, jnp.zeros((), ks.dtype), ks)
                diff = diff | (ns != jnp.roll(ns, 1))
        diff = diff | (ks != jnp.roll(ks, 1))
    new_group = umask_s & (diff | (pos == 0))
    seg = jnp.maximum(jnp.cumsum(new_group) - 1, 0)
    seg = jnp.where(umask_s, seg, ucap)  # sentinel for excluded rows
    gid = jnp.zeros(ucap, dtype=jnp.int64).at[perm].set(seg)
    return gid[:pcap], gid[pcap:]


def _hash_gids(probe_keys, build_keys, p_pad, b_pad,
               null_equal: bool = False):
    """Hash-table alternative to `_union_gids`: build keys claim slots
    in a scatter-claim table (ops/hashtable.py), gid = dense build-key
    group id; probe rows look their gid up with lock-step probe rounds.

    Duplicate build keys are the NORMAL case (they share a slot, and the
    downstream per-gid [start, count) expansion emits every duplicate) —
    the reference's hash-join behavior (bodo/libs/_hash_join.cpp),
    realized as parallel scatter/gather rounds instead of serial chains.
    Costs O(rounds) scatters over the BUILD side plus one bcap-row sort
    downstream, vs the union sort's O((P+B) log (P+B)) — the win when
    the probe side dwarfs the build side (FK joins).

    Returns (gid_p, gid_b, unresolved); sentinel gid == pcap + bcap for
    excluded/unmatched rows, matching the union convention. `unresolved`
    True → the probe-round cap was hit; caller must use the sort path."""
    from bodo_tpu.ops import hashtable as HT

    pcap = probe_keys[0][0].shape[0]
    bcap = build_keys[0][0].shape[0]
    ucap = pcap + bcap
    pcodes, bcodes, p_ok0, b_ok0 = HT.aligned_codes(probe_keys,
                                                    build_keys, null_equal)
    b_ok = b_pad if b_ok0 is None else (b_pad & b_ok0)
    p_ok = p_pad if p_ok0 is None else (p_pad & p_ok0)
    T = HT.table_size(bcap)
    slot_b, owner, _r, un1 = HT.claim_slots(bcodes, b_ok, T)
    seg_b, _group_row, ng = HT.densify(slot_b, owner, T)
    bidx, un2 = HT.probe_slots(bcodes, owner, pcodes, p_ok, T)
    gid_b = jnp.where(b_ok, seg_b.astype(jnp.int64), ucap)
    gid_p = jnp.where(bidx >= 0,
                      seg_b[jnp.maximum(bidx, 0)].astype(jnp.int64), ucap)
    return gid_p, gid_b, un1 | un2


def _join_plan(probe_keys, build_keys, probe_count, build_count,
               how: str, null_equal: bool = False, method: str = "sort"):
    pcap = probe_keys[0][0].shape[0]
    bcap = build_keys[0][0].shape[0]
    ucap = pcap + bcap
    p_pad = K.row_mask(probe_count, pcap)
    b_pad = K.row_mask(build_count, bcap)
    if method == "hash":
        gid_p, gid_b, unresolved = _hash_gids(probe_keys, build_keys,
                                              p_pad, b_pad, null_equal)
    else:
        gid_p, gid_b = _union_gids(probe_keys, build_keys, p_pad, b_pad,
                                   null_equal)
        unresolved = jnp.zeros((), bool)

    # order build rows by gid (sentinel rows last)
    b_perm = SE.stable_argsort([(gid_b.astype(jnp.uint32), 32)])
    gid_b_s = gid_b[b_perm]
    bc = jax.ops.segment_sum(jnp.ones(bcap, dtype=jnp.int64),
                             jnp.minimum(gid_b, ucap),
                             num_segments=ucap + 1)
    bc = bc.at[ucap].set(0)  # sentinel gid matches nothing
    starts = jnp.cumsum(bc) - bc

    keyed = gid_p < ucap  # real probe rows with non-null keys
    matches = jnp.where(keyed, bc[jnp.minimum(gid_p, ucap)], 0)
    if how in ("left", "outer"):
        L = jnp.where(p_pad, jnp.maximum(matches, 1), 0)
    else:  # inner
        L = matches
    offsets = jnp.cumsum(L) - L
    total = jnp.sum(L)

    # full outer: build rows whose gid no real keyed probe row shares are
    # appended after the probe-driven rows (null-key build rows — gid ==
    # sentinel — never match, so they are unmatched too, SQL semantics)
    unm_idx = None
    n_unm = jnp.zeros((), jnp.int64)
    if how == "outer":
        pc_per_gid = jax.ops.segment_sum(
            jnp.where(p_pad & keyed, 1, 0).astype(jnp.int64),
            jnp.minimum(gid_p, ucap), num_segments=ucap + 1)
        unmatched_b = b_pad & (
            (gid_b >= ucap) | (pc_per_gid[jnp.minimum(gid_b, ucap)] == 0))
        (unm_idx,), n_unm = K.compact(unmatched_b,
                                      (jnp.arange(bcap, dtype=jnp.int64),))
        total = total + n_unm
    return (gid_p, b_perm, bc, starts, offsets, L, total, p_pad,
            unm_idx, n_unm, unresolved)


@bounded_jit(static_argnames=("num_keys", "how", "null_equal", "method"))
def join_count(probe_keys, build_keys, probe_count, build_count,
               num_keys: int, how: str, null_equal: bool = False,
               method: str = "sort"):
    """Exact output row count of the join (cheap pre-pass; the host uses
    it to pick the materialization capacity bucket). Returns
    (total, unresolved) — unresolved only ever True for method='hash'."""
    plan = _join_plan(probe_keys, build_keys, probe_count,
                      build_count, how, null_equal, method)
    return plan[6], plan[10]


@bounded_jit(static_argnames=("num_keys", "how", "out_capacity",
                              "null_equal", "method"))
def join_local(probe_arrays, build_arrays, probe_count, build_count,
               num_keys: int, how: str, out_capacity: int,
               null_equal: bool = False, method: str = "sort"):
    """Materialize the equi-join.

    probe_arrays/build_arrays: tuples of (data, valid); the first
    `num_keys` of each are the join keys (positionally aligned).
    Returns (out_probe, out_build, out_count, overflow, unresolved):
      out_probe — all probe columns gathered per output row,
      out_build — all build columns (valid=False on unmatched left rows),
      overflow — True if out_capacity was too small (host retries bigger),
      unresolved — method='hash' hit its probe-round cap (pathological
      input; host must re-run with method='sort').
    """
    probe_keys = probe_arrays[:num_keys]
    build_keys = build_arrays[:num_keys]
    (gid_p, b_perm, bc, starts, offsets, L, total, p_pad,
     unm_idx, n_unm, unresolved) = _join_plan(
        probe_keys, build_keys, probe_count, build_count, how, null_equal,
        method)
    ucap = gid_p.shape[0] + b_perm.shape[0]
    bcap = b_perm.shape[0]
    total_probe = total - n_unm  # probe-driven rows (== total unless outer)

    j = jnp.arange(out_capacity)
    live = j < total
    probe_row = live & (j < total_probe)
    pidx = jnp.clip(jnp.searchsorted(offsets, j, side="right") - 1,
                    0, gid_p.shape[0] - 1)
    k = j - offsets[pidx]
    g = jnp.minimum(gid_p[pidx], ucap)
    matched = probe_row & (k < bc[g])
    bpos = jnp.clip(starts[g] + k, 0, bcap - 1)
    bidx = b_perm[bpos]
    if how == "outer":
        # appended unmatched-build rows: slots [total_probe, total)
        appended = live & (j >= total_probe)
        k_app = jnp.clip(j - total_probe, 0, bcap - 1)
        bidx = jnp.where(appended, unm_idx[k_app], bidx)
        build_emit = matched | appended
    else:
        build_emit = matched

    out_probe = []
    for d, v in probe_arrays:
        od = jnp.where(probe_row, d[pidx], jnp.zeros((), d.dtype))
        base_v = probe_row if v is None else (probe_row & v[pidx])
        # probe columns are nullable on appended build-only rows
        ov = base_v if how == "outer" else (
            None if v is None else base_v)
        out_probe.append((od, ov))
    out_build = []
    for d, v in build_arrays:
        od = jnp.where(build_emit, d[bidx], jnp.zeros((), d.dtype))
        base_v = build_emit if v is None else (build_emit & v[bidx])
        # build side columns are nullable after a left/outer join
        ov = base_v if how in ("left", "outer") else (
            None if v is None else base_v)
        out_build.append((od, ov))
    out_count = jnp.minimum(total, out_capacity)
    overflow = total > out_capacity
    return (tuple(out_probe), tuple(out_build), out_count, overflow,
            unresolved)


@bounded_jit(static_argnames=("out_capacity",))
def cross_local(probe_arrays, build_arrays, probe_count, build_count,
                out_capacity: int):
    """Cartesian product in pandas row order (probe-major: each probe row
    paired with every build row in order). The host computes the exact
    output size (nl * nr) up front, so there is no overflow retry —
    reference analogue: bodo/libs/_nested_loop_join_impl.cpp's block
    product, here a static index transform instead of a loop."""
    pcap = probe_arrays[0][0].shape[0]
    bcap = build_arrays[0][0].shape[0]
    total = probe_count * build_count
    nb = jnp.maximum(build_count, 1)
    j = jnp.arange(out_capacity)
    live = j < total
    pidx = jnp.clip(j // nb, 0, pcap - 1)
    bidx = jnp.clip(j % nb, 0, bcap - 1)

    def _gather(arrays, idx):
        out = []
        for d, v in arrays:
            od = jnp.where(live, d[idx], jnp.zeros((), d.dtype))
            ov = None if v is None else (live & v[idx])
            out.append((od, ov))
        return tuple(out)

    return (_gather(probe_arrays, pidx), _gather(build_arrays, bidx),
            jnp.minimum(total, out_capacity))
