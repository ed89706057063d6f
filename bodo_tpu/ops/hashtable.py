"""XLA scatter-claim hash table: arbitrary-cardinality group ids and
join LUTs without sorting.

TPU-native replacement for the reference's serial-chaining hash tables
(bodo/libs/_hash_join.cpp, bodo/libs/groupby/_groupby.cpp): instead of
per-row insert chains, all rows claim table slots IN PARALLEL with a
scatter-min, and unresolved rows re-probe in lock-step rounds (double
hashing). Every round is a handful of dense scatters/gathers — exactly
the ops XLA lowers well on TPU — and the expected round count at load
factor ≤ 0.5 is small (longest probe chain, O(log U)).

The claim table is sized 2×capacity so no cardinality estimate and no
overflow retry is needed; the table itself is one int32 array (the
claiming row id per slot), so its memory cost is 8 bytes/row. Group ids
are then re-densified to [0, n_groups) with a cumsum so downstream
segment-reductions run over a capacity-sized space, not the table.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bodo_tpu.ops import sort_encoding as SE
from bodo_tpu.utils.kernel_cache import bounded_jit

# murmur3 fmix64 constants — the standard 64-bit avalanche finalizer
_M1 = np.uint64(0xFF51AFD7ED558CCD)
_M2 = np.uint64(0xC4CEB9FE1A85EC53)
_GOLD = np.uint64(0x9E3779B97F4A7C15)  # 2^64/phi, for multi-key combine

# rows that fail to resolve within this many probe rounds trigger the
# caller's sort-based fallback (practically unreachable at load 0.5)
MAX_ROUNDS = 64


def _fmix64(x):
    x = x ^ (x >> np.uint64(33))
    x = x * _M1
    x = x ^ (x >> np.uint64(33))
    x = x * _M2
    return x ^ (x >> np.uint64(33))


def combine_hash(codes: Sequence) -> jax.Array:
    """One uint64 hash per row from bijective per-column uint64 codes."""
    h = jnp.full(codes[0].shape, np.uint64(0x5851F42D4C957F2D))
    for c in codes:
        h = _fmix64(h ^ c) + _GOLD
    return _fmix64(h)


def encode_columns(key_arrays: Sequence[Tuple], null_equal: bool = True):
    """(codes, ok) for hashing/equality.

    codes: one bijective uint64 per key column; when `null_equal`, nulls
    get a dedicated extra 0/1 code column (null == null, and no real
    value can collide with the null group). When not `null_equal`,
    null-keyed rows are excluded via `ok` (pandas groupby dropna /
    SQL join semantics).

    A float64 column's code is its bits, which the v5e's compiler
    refuses (`sort_encoding.encode_value`): `relational.groupby_agg`
    sends a key list that holds one to the sort route instead."""
    codes = []
    ok = None
    for data, valid in key_arrays:
        enc = SE.encode_value(data)
        null = SE.null_flag(data, valid)
        if null is not None:
            if null_equal:
                codes.append(null.astype(jnp.uint64))
                enc = jnp.where(null, np.uint64(0), enc)
            else:
                nn = ~null
                ok = nn if ok is None else (ok & nn)
        codes.append(enc)
    return tuple(codes), ok


def encode_columns_aligned(key_arrays: Sequence[Tuple],
                           null_cols: Sequence[bool],
                           null_equal: bool = True):
    """Like encode_columns, but with a caller-fixed per-key null-column
    layout so two sides of a join encode to STRUCTURALLY IDENTICAL code
    tuples even when only one side is nullable. `null_cols[i]` is True
    when key i gets a null code column (must be the OR of both sides'
    nullability)."""
    codes = []
    ok = None
    for (data, valid), want_null in zip(key_arrays, null_cols):
        enc = SE.encode_value(data)
        null = SE.null_flag(data, valid)
        if null is None and want_null:
            null = jnp.zeros(data.shape, bool)
        if null is not None:
            if null_equal:
                codes.append(null.astype(jnp.uint64))
                enc = jnp.where(null, np.uint64(0), enc)
            else:
                nn = ~null
                ok = nn if ok is None else (ok & nn)
        codes.append(enc)
    return tuple(codes), ok


def aligned_codes(probe_keys: Sequence[Tuple], build_keys: Sequence[Tuple],
                  null_equal: bool):
    """Encode two positionally-aligned key sets into STRUCTURALLY
    IDENTICAL code tuples: build keys cast to the probe dtypes, and both
    sides share one null-column layout (the OR of their nullability).
    The one spelling of the hash-join/membership encode used by
    ops/join.py `_hash_gids` and the streaming drain's key-membership
    probe. Returns (pcodes, bcodes, p_ok, b_ok) with ok = None when no
    rows are excluded."""
    bkeys = tuple((bd.astype(pd_.dtype), bv)
                  for (pd_, _pv), (bd, bv) in zip(probe_keys, build_keys))
    null_cols = tuple(
        SE.null_flag(pd_, pv) is not None
        or SE.null_flag(bd, bv) is not None
        for (pd_, pv), (bd, bv) in zip(probe_keys, bkeys))
    bcodes, b_ok = encode_columns_aligned(bkeys, null_cols, null_equal)
    pcodes, p_ok = encode_columns_aligned(probe_keys, null_cols,
                                          null_equal)
    return pcodes, bcodes, p_ok, b_ok


def table_size(capacity: int) -> int:
    """Power-of-two claim-table size at load factor ≤ 0.5."""
    t = 16
    while t < 2 * max(capacity, 1):
        t <<= 1
    return t


@bounded_jit(static_argnames=("T", "max_rounds"))
def claim_slots(codes: Tuple, ok, T: int, max_rounds: int = MAX_ROUNDS):
    """Assign every ok row a slot in [0, T): equal keys share a slot,
    distinct keys get distinct slots.

    Returns (slot int32[N] (-1 for !ok), owner int32[T] (claiming row id
    per slot, -1 empty), rounds_used int32, unresolved bool — True means
    some row never resolved (caller must fall back)."""
    n = codes[0].shape[0]
    mask = np.uint64(T - 1)
    h = combine_hash(codes)
    # odd step → the probe sequence cycles through all T slots
    step = (_fmix64(h ^ _GOLD) | np.uint64(1)) & mask
    h = h & mask
    rows = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(np.iinfo(np.int32).max)

    def cond(state):
        r, slot, owner = state
        return (r < max_rounds) & jnp.any(slot == -1)

    def body(state):
        r, slot, owner = state
        un = slot == -1
        p = ((h + r.astype(jnp.uint64) * step) & mask).astype(jnp.int32)
        # claim: the smallest probing row id wins each still-empty slot
        cand = jnp.where(un, rows, big)
        claim = jnp.full(T, big, jnp.int32).at[p].min(cand)
        owner = jnp.where((owner < 0) & (claim < big),
                          claim, owner)
        # match: probing rows whose slot owner holds an equal key resolve
        o = owner[p]
        osafe = jnp.maximum(o, 0)
        eq = o >= 0
        for c in codes:
            eq = eq & (c[osafe] == c)
        slot = jnp.where(un & eq, p, slot)
        return r + jnp.uint32(1), slot, owner

    slot0 = jnp.where(ok, jnp.int32(-1), jnp.int32(-2))
    owner0 = jnp.full(T, -1, jnp.int32)
    r, slot, owner = lax.while_loop(
        cond, body, (jnp.uint32(0), slot0, owner0))
    unresolved = jnp.any(slot == -1)
    # drop slots claimed only by rows that later resolved elsewhere is
    # impossible: a slot's owner resolves TO that slot in the round it
    # claims (it matches itself), so every owned slot is a live group
    return jnp.where(slot < 0, -1, slot), owner, r, unresolved


@bounded_jit(static_argnames=("T",))
def densify(slot, owner, T: int):
    """Map claim-table slots to dense group ids [0, n_groups).

    Returns (seg int32[N] — dense group id per row, group id = n for
    !ok rows; group_row int32[cap] — a representative source row per
    dense group id, packed at the front; n_groups)."""
    n = slot.shape[0]
    present = owner >= 0
    newid = (jnp.cumsum(present.astype(jnp.int32)) - 1)
    n_groups = newid[-1] + 1
    seg = jnp.where(slot >= 0, newid[jnp.maximum(slot, 0)], n)
    # representative row per dense group (scatter; ids are unique)
    group_row = jnp.full(n, -1, jnp.int32).at[
        jnp.where(present, newid, n)].set(
        jnp.maximum(owner, 0), mode="drop")
    return seg, group_row, n_groups


def group_ids(key_arrays: Sequence[Tuple], ok_rows,
              max_rounds: int = MAX_ROUNDS):
    """End-to-end: dense pandas-dropna group ids for arbitrary keys.

    key_arrays: [(data, valid), ...]; ok_rows: bool[cap] live-row mask.
    Returns (seg int32[cap] in [0, n_groups) (== cap for dropped rows),
    group_row int32[cap], n_groups, unresolved)."""
    codes, null_ok = encode_columns(key_arrays, null_equal=False)
    ok = ok_rows if null_ok is None else (ok_rows & null_ok)
    cap = codes[0].shape[0]
    T = table_size(cap)
    slot, owner, _r, unresolved = claim_slots(codes, ok, T, max_rounds)
    seg, group_row, n_groups = densify(slot, owner, T)
    return seg, group_row, n_groups, unresolved


# ---------------------------------------------------------------------------
# hash join LUT (unique build keys; dup-build falls back to sort-merge)
# ---------------------------------------------------------------------------

@bounded_jit(static_argnames=("T", "max_rounds"))
def probe_slots(build_codes: Tuple, owner, probe_codes: Tuple, ok,
                T: int, max_rounds: int = MAX_ROUNDS):
    """For each probe row, the build row with an equal key, else -1.

    Follows the same double-hash probe sequence as claim_slots; a probe
    terminates on key match (hit) or empty slot (miss). Returns
    (idx int32[M], unresolved bool)."""
    m = probe_codes[0].shape[0]
    mask = np.uint64(T - 1)
    h = combine_hash(probe_codes)
    step = (_fmix64(h ^ _GOLD) | np.uint64(1)) & mask
    h = h & mask

    # pallas route: the whole probe walk as one kernel (slot gather +
    # 64-bit key compare on the MXU). Gate read at trace time — tests
    # flipping FORCE_INTERPRET clear probe_slots.cache.
    from bodo_tpu.ops import pallas_kernels as PK
    res = PK.hash_probe(build_codes, owner, probe_codes, ok, h, step,
                        T, max_rounds)
    if res is not None:
        return res

    def cond(state):
        r, idx, active = state
        return (r < max_rounds) & jnp.any(active)

    def body(state):
        r, idx, active = state
        p = ((h + r.astype(jnp.uint64) * step) & mask).astype(jnp.int32)
        o = owner[p]
        osafe = jnp.maximum(o, 0)
        eq = o >= 0
        for bc, pc in zip(build_codes, probe_codes):
            eq = eq & (bc[osafe] == pc)
        hit = active & eq
        miss = active & (o < 0)
        idx = jnp.where(hit, o, idx)
        active = active & ~hit & ~miss
        return r + jnp.uint32(1), idx, active

    idx0 = jnp.full(m, -1, jnp.int32)
    r, idx, active = lax.while_loop(
        cond, body, (jnp.uint32(0), idx0, ok))
    return idx, jnp.any(active)
