"""Pallas TPU kernels for relational hot paths.

The first kernel family targets the dense groupby accumulate: on TPU,
XLA lowers `segment_sum` with random slot ids to scatter-adds, which
serialize on the VPU. For small slot spaces the MXU is the right unit —
aggregation by one-hot matmul: a [BLK, K] one-hot of the slot codes
contracted against the value block accumulates all columns of a block in
one 128x128-systolic pass (the standard TPU histogram/segment-reduce
recipe). This is the TPU-native replacement for the reference's
hash-table accumulate loop (bodo/libs/groupby/_groupby.cpp update step).

Kernels run on TPU only (gated by `use_pallas()`); every caller keeps an
XLA body for shapes outside a kernel's gate, correctness is tested on
CPU through `interpret=True`, and tests/test_chip_compile.py compiles
each kernel for a described v5e.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# row block per grid step: onehot f32 [BLK, K<=MAX_SLOTS] must fit VMEM
_BLK = 512
MAX_MATMUL_SLOTS = 4096

_I0 = np.int32(0)  # int32 BlockSpec index constant (see in_specs comment)

# every [N, 1] (or [N, <128]) operand is laid out 128 lanes wide in HBM,
# 512 bytes a row (the v5e compiler's memory_analysis shows it as
# temporaries); a gate admits only row counts whose padded operands fit
_PADDED_ROW_BYTES = 512
_PADDED_BUDGET = 8 << 30


def _rows_fit(n: int, n_operands: int) -> bool:
    return n * n_operands * _PADDED_ROW_BYTES <= _PADDED_BUDGET

# test hook: run kernels through the pallas interpreter on CPU
FORCE_INTERPRET = False


# number of times the pallas MXU path was TRACED into a jitted groupby
# or fused pipeline stage (trace-time, not per-execution:
# dense_accumulate is only called from inside jit-compiled bodies, so
# this counts compiled-in engagements — interpret-mode traces included,
# since FORCE_INTERPRET runs the same kernel through the pallas
# interpreter). Exported as the metrics registry's
# bodo_tpu_pallas_traced_into_pipeline gauge.
trace_count = 0

# per-kernel-family trace engagement (same trace-time semantics as
# trace_count, so the probe/partition/decode kernels each prove
# engagement separately)
trace_counts = {"groupby": 0, "gather": 0, "probe": 0, "partition": 0,
                "decode": 0, "range": 0}


def _engage(family: str) -> None:
    global trace_count
    trace_count += 1
    trace_counts[family] = trace_counts.get(family, 0) + 1


def reset_trace_counts() -> None:
    for k in trace_counts:
        trace_counts[k] = 0


def use_pallas() -> bool:
    """Pallas kernels engage only on real TPU backends. A kernel that a
    gate admits and the chip's compiler refuses raises: nothing demotes
    the process to the XLA bodies behind its back."""
    return jax.devices()[0].platform == "tpu"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# one MXU kernel, few (n_slots, n_cols) signatures per query shape
# shardcheck: ignore[unregistered-jit]
@functools.partial(jax.jit,
                   static_argnames=("n_slots", "n_cols", "interpret"))
def matmul_groupby_sum(codes, vals, n_slots: int, n_cols: int,
                       interpret: bool = False):
    """Sum `vals` ([N, n_cols] f32, pre-masked) into `n_slots` groups via
    one-hot MXU contraction. codes: int32 [N] in [0, n_slots); rows to be
    ignored must carry zeroed vals (any code). Returns [n_slots, n_cols]
    f32 sums."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = codes.shape[0]
    k_pad = _round_up(max(n_slots, 128), 128)
    c_pad = _round_up(max(n_cols, 8), 8)
    n_pad = _round_up(max(n, _BLK), _BLK)
    if n_pad != n:
        codes = jnp.concatenate(
            [codes, jnp.zeros((n_pad - n,), codes.dtype)])
        vals = jnp.concatenate(
            [vals, jnp.zeros((n_pad - n, vals.shape[1]), vals.dtype)])
    if c_pad != vals.shape[1]:
        vals = jnp.concatenate(
            [vals, jnp.zeros((vals.shape[0], c_pad - vals.shape[1]),
                             vals.dtype)], axis=1)
    # codes ride as a 2-D [N, 1] block: 1-D BlockSpecs fail Mosaic
    # legalization on current libtpu toolchains (func.return on the
    # implicit scalar layout), and TPU vregs are 2-D (8x128) anyway
    codes2 = codes[:, None]

    def kernel(codes_ref, vals_ref, out_ref, acc_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        codes_blk = codes_ref[:]                      # [BLK, 1]
        onehot = (codes_blk ==
                  jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
                  ).astype(jnp.float32)               # [BLK, K]
        # [C, BLK] @ [BLK, K] -> [C, K] on the MXU. HIGHEST precision:
        # the default bf16 MXU pass rounds the f32 values (~0.4% rel
        # error on sums); the one-hot side is exact either way, so the
        # bf16x3 decomposition restores ~f32 accuracy for the val side
        acc_ref[:] += jax.lax.dot_general(
            vals_ref[:].T, onehot,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

        @pl.when(step == pl.num_programs(0) - 1)
        def _flush():
            out_ref[:] = acc_ref[:]

    # traced inside the jitted matmul_groupby_sum above — cached by
    # its jit signature  # shardcheck: ignore[unregistered-jit]
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // _BLK,),
        # index-map constants must be int32: under jax_enable_x64 (which
        # the engine needs for int64 ticks) a bare Python 0 becomes an
        # i64, and Mosaic fails to legalize the mixed (i32, i64) return
        in_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, c_pad), lambda i: (i, _I0)),
        ],
        out_specs=pl.BlockSpec((c_pad, k_pad), lambda i: (_I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, k_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c_pad, k_pad), jnp.float32)],
        interpret=interpret,
    )(codes2, vals)
    return out[:n_cols, :n_slots].T                   # [n_slots, n_cols]


# one-hot gathers are exact in f32 only while the gathered values fit
# the 24-bit mantissa; callers carry row indices, so this bounds nrows
MAX_GATHER_VALUE = 1 << 24


# shardcheck: ignore[unregistered-jit]
@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def _matmul_gather_kernel(codes, lut, n_slots: int,
                          interpret: bool = False):
    """lut[codes] by one-hot MXU contraction: a [BLK, K] one-hot of the
    slot codes contracted against the f32 LUT column. codes: int32 [N]
    in [0, n_slots); lut: int32 [n_slots] with values in
    (-MAX_GATHER_VALUE, MAX_GATHER_VALUE) so the f32 pass is exact.
    Returns int32 [N]."""
    from jax.experimental import pallas as pl

    n = codes.shape[0]
    k_pad = _round_up(max(n_slots, 128), 128)
    n_pad = _round_up(max(n, _BLK), _BLK)
    if n_pad != n:
        codes = jnp.concatenate(
            [codes, jnp.zeros((n_pad - n,), codes.dtype)])
    lutf = jnp.zeros((k_pad, 1), jnp.float32).at[:n_slots, 0].set(
        lut.astype(jnp.float32))
    codes2 = codes[:, None]                           # 2-D, see above

    def kernel(codes_ref, lut_ref, out_ref):
        codes_blk = codes_ref[:]                      # [BLK, 1]
        onehot = (codes_blk ==
                  jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
                  ).astype(jnp.float32)               # [BLK, K]
        # [BLK, K] @ [K, 1] -> [BLK, 1]: exactly one lut row per code,
        # so the f32 contraction reproduces the int32 value exactly
        out_ref[:] = jax.lax.dot_general(
            onehot, lut_ref[:],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    # shardcheck: ignore[unregistered-jit]
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // _BLK,),
        in_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((k_pad, 1), lambda i: (_I0, _I0)),
        ],
        out_specs=pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(codes2, lutf)
    return out[:n, 0].astype(jnp.int32)


def matmul_gather(codes, lut, interpret: Optional[bool] = None):
    """Gather ``lut[codes]`` (the dense-LUT hash-probe lookup step).

    TPU (or interpret=True) with a LUT small enough for the one-hot
    MXU pass: the pallas kernel above. Elsewhere: the plain XLA gather.
    Callers must keep lut values within (-MAX_GATHER_VALUE,
    MAX_GATHER_VALUE) — they are row indices plus the -1 empty marker,
    so this caps the build side at 16M rows (checked by the caller's
    gate, not here)."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if ((use_pallas() or interp) and lut.shape[0] <= MAX_MATMUL_SLOTS
            and _rows_fit(codes.shape[0], 2)):
        _engage("gather")
        return _matmul_gather_kernel(codes, lut, lut.shape[0],
                                     interpret=interp)
    return lut[codes]


def bucket_counts(dest, ok, num_buckets: int,
                  interpret: Optional[bool] = None):
    """Per-destination row histogram (the bucket-partition counting
    step of the fixed-capacity shuffle): count rows with ok set per
    dest shard. On TPU the scatter-add that XLA lowers segment_sum to
    serializes on the VPU, so this routes through the same one-hot MXU
    accumulate as the dense groupby. Exact while the per-bucket count
    stays under MAX_GATHER_VALUE (f32 mantissa), which the row-count
    gate guarantees. Returns int32 [num_buckets]."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if ((use_pallas() or interp) and num_buckets <= MAX_MATMUL_SLOTS
            and dest.shape[0] < MAX_GATHER_VALUE
            and _rows_fit(dest.shape[0], 2)):
        _engage("partition")
        vals = ok.astype(jnp.float32)[:, None]
        sums = matmul_groupby_sum(dest.astype(jnp.int32), vals,
                                  num_buckets, 1, interpret=interp)
        return sums[:, 0].astype(jnp.int32)
    return jax.ops.segment_sum(ok.astype(jnp.int32),
                               dest.astype(jnp.int32),
                               num_segments=num_buckets)


def dense_accumulate(codes, cols: Sequence, ok_masks: Sequence,
                     n_slots: int, interpret: Optional[bool] = None):
    """Sum each (column, mask) pair into dense slots.

    TPU (or interpret=True): one fused MXU one-hot matmul over all
    columns. Elsewhere: per-column XLA segment_sum (scatter). Returns a
    list of f32/f64 [n_slots] arrays aligned with `cols`."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if ((use_pallas() or interp) and n_slots <= MAX_MATMUL_SLOTS
            and _rows_fit(codes.shape[0], 2)):
        _engage("groupby")
        vals = jnp.stack(
            [jnp.where(ok, c, 0).astype(jnp.float32)
             for c, ok in zip(cols, ok_masks)], axis=1)
        sums = matmul_groupby_sum(codes, vals, n_slots, len(cols),
                                  interpret=interp)
        return [sums[:, i] for i in range(len(cols))]
    return [jax.ops.segment_sum(jnp.where(ok, c, 0).astype(jnp.float64),
                                codes, num_segments=n_slots)
            for c, ok in zip(cols, ok_masks)]


# ---------------------------------------------------------------------------
# hash-probe loop (open-addressing slot search on the MXU)
# ---------------------------------------------------------------------------

def _split_u64_planes(codes: Sequence) -> jax.Array:
    """Split uint64 code columns into f32 16-bit planes [N, 4*len].

    Two uint64s are equal iff all four of their 16-bit planes are equal,
    and every plane value (< 2^16) is exact in f32 — so a one-hot MXU
    gather of the planes supports exact 64-bit key comparison."""
    planes = []
    for c in codes:
        for k in range(4):
            planes.append(((c >> np.uint64(16 * k))
                           & np.uint64(0xFFFF)).astype(jnp.float32))
    return jnp.stack(planes, axis=1)


# shardcheck: ignore[unregistered-jit]
@functools.partial(jax.jit, static_argnames=("T", "n_planes",
                                             "max_rounds", "interpret"))
def _hash_probe_kernel(h_m, step_m, probe_planes, active0, slot_tab,
                       T: int, n_planes: int, max_rounds: int,
                       interpret: bool = False):
    """Open-addressing probe loop in one kernel: each round gathers the
    probed slot's (owner, key planes) row with a single one-hot MXU
    matmul and resolves hits/misses in registers — the whole double-hash
    walk stays on-chip instead of one XLA gather dispatch per round.

    h_m/step_m: int32 [N] hash and step already reduced mod T (the probe
    sequence (h + r*step) mod T only needs the low bits, so int32
    arithmetic is exact). slot_tab: f32 [T, 1+n_planes] — column 0 is
    the owning build row per slot (-1 empty), the rest are the slot
    key's 16-bit planes. Returns (idx f32 [N,1], still_active f32
    [N,1])."""
    from jax.experimental import pallas as pl

    n = h_m.shape[0]
    k_pad = _round_up(max(T, 128), 128)
    c_pad = _round_up(max(1 + n_planes, 128), 128)
    p_pad = _round_up(max(n_planes, 128), 128)
    n_pad = _round_up(max(n, _BLK), _BLK)

    def pad_rows(a):
        if a.shape[0] == n_pad:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((n_pad - a.shape[0],) + a.shape[1:], a.dtype)])

    h2 = pad_rows(h_m[:, None])
    s2 = pad_rows(step_m[:, None])
    pp = pad_rows(jnp.pad(probe_planes,
                          ((0, 0), (0, p_pad - n_planes))))
    act = pad_rows(active0.astype(jnp.float32)[:, None])
    tab = jnp.zeros((k_pad, c_pad), jnp.float32)
    tab = tab.at[:T, :1 + n_planes].set(slot_tab)
    maskT = np.int32(T - 1)

    def kernel(hm_ref, sm_ref, pp_ref, act_ref, tab_ref, idx_ref,
               unres_ref):
        hm = hm_ref[:]
        sm = sm_ref[:]
        ppb = pp_ref[:]

        def cond(st):
            r, idx, active = st
            # a 32-bit reduction: Mosaic has no scalar from the bool
            # any() that x64 mode widens
            return (r < max_rounds) & (jnp.max(active) > np.float32(0))

        def body(st):
            r, idx, active = st
            p = jnp.bitwise_and(hm + r * sm, maskT)         # [BLK, 1]
            onehot = (p == jax.lax.broadcasted_iota(
                jnp.int32, (1, k_pad), 1)).astype(jnp.float32)
            g = jax.lax.dot_general(
                onehot, tab_ref[:],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)        # [BLK, C]
            o = g[:, 0:1]
            eq = o >= 0
            for j in range(n_planes):
                eq = eq & (g[:, 1 + j:2 + j] == ppb[:, j:j + 1])
            live = active > 0
            hit = live & eq
            miss = live & (o < 0)
            idx = jnp.where(hit, o, idx)
            active = jnp.where(hit | miss, 0.0, active)
            return r + np.int32(1), idx, active

        idx0 = jnp.full(hm.shape, -1.0, jnp.float32)
        _r, idx, active = jax.lax.while_loop(
            cond, body, (np.int32(0), idx0, act_ref[:]))
        idx_ref[:] = idx
        unres_ref[:] = active

    # shardcheck: ignore[unregistered-jit]
    idx, unres = pl.pallas_call(
        kernel,
        grid=(n_pad // _BLK,),
        in_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, p_pad), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((k_pad, c_pad), lambda i: (_I0, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(h2, s2, pp, act, tab)
    return idx[:n, 0], unres[:n, 0]


def hash_probe(build_codes: Sequence, owner, probe_codes: Sequence, ok,
               h, step, T: int, max_rounds: int,
               interpret: Optional[bool] = None):
    """Pallas route for ops/hashtable.probe_slots: the open-addressing
    slot search as ONE kernel (per-round slot gather + 64-bit key
    compare on the MXU via 16-bit planes). `h`/`step` are the caller's
    uint64 double-hash sequence parameters. Returns (idx int32 [N],
    unresolved bool) or None when the gate is closed (caller keeps its
    XLA while_loop)."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if not ((use_pallas() or interp) and T <= MAX_MATMUL_SLOTS
            and T // 2 < MAX_GATHER_VALUE and _rows_fit(h.shape[0], 6)):
        return None
    _engage("probe")
    maskT = np.uint64(T - 1)
    h_m = (h & maskT).astype(jnp.int32)
    step_m = (step & maskT).astype(jnp.int32)
    # slot table: owner + the slot key's planes (gathered once, XLA)
    osafe = jnp.maximum(owner, 0)
    slot_planes = _split_u64_planes([c[osafe] for c in build_codes])
    slot_tab = jnp.concatenate(
        [owner.astype(jnp.float32)[:, None], slot_planes], axis=1)
    probe_planes = _split_u64_planes(list(probe_codes))
    idx, unres = _hash_probe_kernel(
        h_m, step_m, probe_planes, ok, slot_tab, T,
        4 * len(probe_codes), max_rounds, interpret=interp)
    return idx.astype(jnp.int32), jnp.any(unres > 0)


# ---------------------------------------------------------------------------
# bucket partition scatter (stable in-bucket rank without a sort)
# ---------------------------------------------------------------------------

# shardcheck: ignore[unregistered-jit]
@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def _partition_rank_kernel(dest, ok, num_buckets: int,
                           interpret: bool = False):
    """Stable in-bucket rank per row + per-bucket counts in one grid
    pass: a block's in-block exclusive rank is a strict-lower-triangular
    matmul against the block's one-hot destination matrix, and a running
    per-bucket base rides in VMEM scratch across blocks (sequential
    grid). Replaces the stable sort the XLA fallback uses to derive
    scatter positions. Exact while ranks stay under the f32 mantissa
    (callers gate rows < MAX_GATHER_VALUE)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = dest.shape[0]
    k_pad = _round_up(max(num_buckets, 128), 128)
    n_pad = _round_up(max(n, _BLK), _BLK)
    if n_pad != n:
        dest = jnp.concatenate(
            [dest, jnp.zeros((n_pad - n,), dest.dtype)])
        ok = jnp.concatenate([ok, jnp.zeros((n_pad - n,), bool)])
    dest2 = dest.astype(jnp.int32)[:, None]
    ok2 = ok.astype(jnp.float32)[:, None]

    def kernel(dest_ref, ok_ref, rank_ref, cnt_ref, base_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            base_ref[:] = jnp.zeros_like(base_ref)

        d = dest_ref[:]                                   # [BLK, 1]
        okf = ok_ref[:]                                   # [BLK, 1]
        onehot = (d == jax.lax.broadcasted_iota(
            jnp.int32, (1, k_pad), 1)).astype(jnp.float32) * okf
        row = jax.lax.broadcasted_iota(jnp.int32, (_BLK, _BLK), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (_BLK, _BLK), 1)
        tri = (row > col).astype(jnp.float32)
        # earlier in-block rows per bucket, then select own column
        prefix = jax.lax.dot_general(
            tri, onehot, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)          # [BLK, K]
        rank_in = jnp.sum(prefix * onehot, axis=1, keepdims=True)
        base_at = jax.lax.dot_general(
            onehot, base_ref[:].T,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)          # [BLK, 1]
        rank_ref[:] = jnp.where(okf > 0, rank_in + base_at, -1.0)
        base_ref[:] += jnp.sum(onehot, axis=0, keepdims=True)

        @pl.when(step == pl.num_programs(0) - 1)
        def _flush():
            cnt_ref[:] = base_ref[:]

    # shardcheck: ignore[unregistered-jit]
    rank, cnt = pl.pallas_call(
        kernel,
        grid=(n_pad // _BLK,),
        in_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
            pl.BlockSpec((1, k_pad), lambda i: (_I0, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, k_pad), jnp.float32)],
        interpret=interpret,
    )(dest2, ok2)
    return (rank[:n, 0].astype(jnp.int32),
            cnt[0, :num_buckets].astype(jnp.int32))


def partition_rank(dest, ok, num_buckets: int,
                   interpret: Optional[bool] = None):
    """Pallas route for the bucket-partition scatter: per-row stable
    in-bucket rank plus per-bucket counts (parallel/shuffle.bucket_rows
    derives scatter positions from this instead of a stable sort; the
    sort sample-partition step shares it). Returns (rank int32 [N],
    counts int32 [num_buckets]) or None when the gate is closed."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if not ((use_pallas() or interp) and num_buckets <= MAX_MATMUL_SLOTS
            and dest.shape[0] < MAX_GATHER_VALUE
            and _rows_fit(dest.shape[0], 3)):
        return None
    _engage("partition")
    return _partition_rank_kernel(dest.astype(jnp.int32), ok,
                                  num_buckets, interpret=interp)


# ---------------------------------------------------------------------------
# dictionary gather (device decode)
# ---------------------------------------------------------------------------

def dict_gather(codes, lut, interpret: Optional[bool] = None):
    """Pallas dictionary gather for decode: ``lut[codes]`` through the
    one-hot MXU kernel (the string-dict rank remap and small numeric
    dictionaries route here). LUT values must fit the f32 mantissa —
    rank LUTs always do (ranks < dictionary length). Returns int32 [N]
    or None when the gate is closed."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    if not ((use_pallas() or interp)
            and lut.shape[0] <= MAX_MATMUL_SLOTS
            and _rows_fit(codes.shape[0], 2)):
        return None
    _engage("decode")
    return _matmul_gather_kernel(codes, lut, lut.shape[0],
                                 interpret=interp)


# ---------------------------------------------------------------------------
# radix/range partition step (uint64 keys via 16-bit planes; ops/sort.py)
# ---------------------------------------------------------------------------

# shardcheck: ignore[unregistered-jit]
@functools.partial(jax.jit, static_argnames=("n_spl", "interpret"))
def _range_partition_kernel(pk_planes, spl_planes, spl_valid, n_spl: int,
                            interpret: bool = False):
    """dest = #(splitters <= pk) by lexicographic 16-bit-plane compare
    (the radix step of the sample sort's range partition): uint64 order
    decided plane-by-plane from the high radix digit down, all in f32
    vector compares — no uint64 arithmetic in the kernel."""
    from jax.experimental import pallas as pl

    n = pk_planes.shape[0]
    s_pad = _round_up(max(n_spl, 128), 128)
    n_pad = _round_up(max(n, _BLK), _BLK)
    if n_pad != n:
        pk_planes = jnp.concatenate(
            [pk_planes, jnp.zeros((n_pad - n, 4), pk_planes.dtype)])
    spl = jnp.zeros((4, s_pad), jnp.float32)
    spl = spl.at[:, :n_spl].set(spl_planes.T)
    sv = jnp.zeros((1, s_pad), jnp.float32).at[0, :n_spl].set(
        spl_valid.astype(jnp.float32))

    def kernel(pp_ref, spl_ref, sv_ref, out_ref):
        pp = pp_ref[:]                                    # [BLK, 4]
        gt = jnp.zeros((_BLK, s_pad), jnp.float32)
        eq = jnp.ones((_BLK, s_pad), jnp.float32)
        for k in (3, 2, 1, 0):                            # high plane first
            pkk = pp[:, k:k + 1]                          # [BLK, 1]
            sk = spl_ref[k:k + 1, :]                      # [1, S]
            gt = jnp.maximum(gt, eq * (pkk > sk).astype(jnp.float32))
            eq = eq * (pkk == sk).astype(jnp.float32)
        ge = jnp.maximum(gt, eq) * sv_ref[:]              # pk >= splitter
        out_ref[:] = jnp.sum(ge, axis=1, keepdims=True)

    # shardcheck: ignore[unregistered-jit]
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // _BLK,),
        in_specs=[
            pl.BlockSpec((_BLK, 4), lambda i: (i, _I0)),
            pl.BlockSpec((4, s_pad), lambda i: (_I0, _I0)),
            pl.BlockSpec((1, s_pad), lambda i: (_I0, _I0)),
        ],
        out_specs=pl.BlockSpec((_BLK, 1), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(pk_planes, spl, sv)
    return out[:n, 0].astype(jnp.int32)


def range_partition(pk, splitters, interpret: Optional[bool] = None):
    """Pallas route for the sample sort's destination assignment:
    ``searchsorted(splitters, pk, side='right')`` over uint64 partition
    keys, decided by 16-bit radix planes in-kernel. Returns int32 [N]
    destinations or None when the gate is closed."""
    interp = bool(interpret) if interpret is not None else FORCE_INTERPRET
    n_spl = splitters.shape[0]
    if not ((use_pallas() or interp) and 0 < n_spl <= MAX_MATMUL_SLOTS
            and _rows_fit(pk.shape[0], 2)):
        return None
    _engage("range")
    pk_planes = _split_u64_planes([pk])
    spl_planes = _split_u64_planes([splitters])
    return _range_partition_kernel(pk_planes, spl_planes,
                                   jnp.ones((n_spl,), bool), n_spl,
                                   interpret=interp)
