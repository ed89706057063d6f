"""Order-preserving unsigned key encodings for multi-key sorts.

The reference sorts with type-dispatched C++ comparators
(bodo/libs/_array_operations.cpp KeyComparisonAsPython). On TPU we instead
map every key column to a uint64 whose unsigned order equals the logical
order (IEEE-754 total-order trick for floats, sign-bit flip for ints,
dictionary codes for strings — dictionaries are kept sorted at ingest so
code order == lexicographic order). Descending keys invert bits.

Nulls and padding rows are NOT folded into the value encoding (clamping
the value range to make room for sentinels collapses distinct extreme
values — e.g. bool False/True, INT64_MIN vs MIN+1). Instead each key
contributes *two* bit fields: a 2-bit rank (padding/null ordering)
followed by the value encoding at the dtype's own width. The fields of
all keys, most significant first, are one bit string per row;
`stable_argsort` orders rows by it.

The TPU compiler's time for one `lax.sort` grows with every key operand
and doubles for 64-bit ones (minutes for a six-key sort), so the bit
string is cut into uint32 words and sorted by successive stable
single-word passes, last word first, inside one `fori_loop`: one
(uint32, int32) sort is compiled whatever the key list is.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

_SIGN64 = np.uint64(0x8000000000000000)


def encode_value(data, ascending: bool = True):
    """uint64 encoding of values; unsigned order == logical order.
    Exact (bijective) — no range clamping.

    Not for a float64 on the v5e: the code is the double's IEEE bits,
    and the TPU compiler cannot bitcast a float64 (it holds one as two
    floats: `UNIMPLEMENTED ... X64 element types ... bitcast-convert`).
    Sort keys go through `encode_field` instead, and
    `relational.groupby_agg` keeps a float64 group key off the hashed
    route, whose codes these are (`hashtable.encode_columns`); float64
    join keys and median / nunique / mode over float64 still come
    here and do not compile there."""
    dt = data.dtype
    if jnp.issubdtype(dt, jnp.floating):
        data = data + jnp.zeros((), dt)  # -0.0 -> +0.0 (equal keys, one code)
        if dt == jnp.float32:
            bits = data.view(jnp.uint32).astype(jnp.uint64) << np.uint64(32)
        else:
            bits = data.view(jnp.uint64)
        sign = (bits & _SIGN64) != 0
        enc = jnp.where(sign, ~bits, bits | _SIGN64)
    elif dt == jnp.bool_:
        enc = data.astype(jnp.uint64)
    elif jnp.issubdtype(dt, jnp.unsignedinteger):
        enc = data.astype(jnp.uint64)
    else:  # signed ints (incl. dict codes, datetimes)
        enc = data.astype(jnp.int64).view(jnp.uint64) ^ _SIGN64
    return ~enc if not ascending else enc


def decode_value(enc, dtype):
    """Inverse of encode_value (ascending form): uint64 codes back to
    values of `dtype` — exact for every supported dtype (the encoding is
    bijective)."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating):
        sign = (enc & _SIGN64) != 0
        bits = jnp.where(sign, enc ^ _SIGN64, ~enc)
        if dt == jnp.float32:
            return (bits >> np.uint64(32)).astype(jnp.uint32) \
                .view(jnp.float32)
        return bits.view(jnp.float64)
    if dt == jnp.bool_:
        return enc != 0
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return enc.astype(dt)
    return (enc ^ _SIGN64).view(jnp.int64).astype(dt)


def null_flag(data, valid=None):
    """Boolean null indicator (explicit mask OR float NaN)."""
    null = None
    if valid is not None:
        null = ~valid
    if jnp.issubdtype(data.dtype, jnp.floating):
        isnan = jnp.isnan(data)
        null = isnan if null is None else (null | isnan)
    return null


def encode_field(data, ascending: bool = True) -> Tuple:
    """(bits, nbits): `encode_value` at the dtype's own width — an
    unsigned array whose low `nbits` bits order like the values.

    float64 is the exception: the TPU compiler cannot bitcast it to
    integer bits (there 64-bit floats are emulated, not stored as IEEE
    words), so its field is the value itself (negated when descending)
    with nbits == 0, and `stable_argsort` gives it a pass of its own."""
    dt = data.dtype
    if dt == jnp.float64:
        canon = data + jnp.zeros((), dt)  # -0.0 -> +0.0
        return (canon if ascending else -canon), 0
    if dt == jnp.bool_:
        enc, nbits = data.astype(jnp.uint8), 1
    else:
        nbits = dt.itemsize * 8
        u = np.dtype(f"uint{nbits}")
        sign = u.type(1 << (nbits - 1))
        if jnp.issubdtype(dt, jnp.floating):
            bits = (data + jnp.zeros((), dt)).view(u)  # -0.0 -> +0.0
            enc = jnp.where((bits & sign) != 0, ~bits, bits | sign)
        elif jnp.issubdtype(dt, jnp.unsignedinteger):
            enc = data
        else:  # signed ints (incl. dict codes, datetimes)
            enc = data.view(u) ^ sign
    if not ascending:
        enc = enc ^ enc.dtype.type((1 << nbits) - 1)
    return enc, nbits


def key_operands(data, valid=None, ascending: bool = True,
                 na_last: bool = True, padmask=None) -> List[Tuple]:
    """Sort fields for one key column: [(rank, 2), (value_enc, nbits)].

    rank orders padding rows last, then nulls per na_last, then real
    values; value_enc breaks ties exactly. Pass the resulting lists
    concatenated to `stable_argsort`.
    """
    field = encode_field(data, ascending)
    null = null_flag(data, valid)
    if null is None and padmask is None:
        return [field]
    if null is not None:
        rank = jnp.where(null, np.uint8(2) if na_last else np.uint8(0), np.uint8(1))
    else:
        rank = jnp.full(data.shape, np.uint8(1), dtype=jnp.uint8)
    if padmask is not None:
        rank = jnp.where(padmask, rank, np.uint8(3))  # padding strictly last
    return [(rank, 2), field]


def _pack_words(fields: Sequence[Tuple]) -> List:
    """Concatenate (bits, nbits) fields, most significant first, into
    uint32 words (the last one left-aligned)."""
    words: List = []
    cur, free = None, 32
    for arr, nbits in fields:
        arr = arr.astype(jnp.uint64 if nbits > 32 else jnp.uint32)
        while nbits > 0:
            take = min(nbits, free)
            nbits -= take
            part = arr >> arr.dtype.type(nbits) if nbits else arr
            if take < 32:
                part = part & part.dtype.type((1 << take) - 1)
            part = part.astype(jnp.uint32)
            cur = part if cur is None else \
                (cur << np.uint32(take)) | part
            free -= take
            if free == 0:
                words.append(cur)
                cur, free = None, 32
    if cur is not None:
        words.append(cur << np.uint32(free))
    return words


def _stable_pass(key, perm):
    return lax.sort((key[perm], perm), num_keys=1, is_stable=True)[1]


def _sort_by_words(words: List, perm):
    if len(words) < 2:
        return _stable_pass(words[0], perm) if words else perm
    stack = jnp.stack(words)

    def one_pass(i, perm):
        w = lax.dynamic_index_in_dim(stack, len(words) - 1 - i, 0,
                                     keepdims=False)
        return _stable_pass(w, perm)

    return lax.fori_loop(0, len(words), one_pass, perm)


def stable_argsort(fields: Sequence[Tuple]):
    """Permutation that stably sorts rows by the concatenation of
    `fields` ((bits, nbits) pairs, most significant first) — what one
    variadic `lax.sort(..., is_stable=True)` over them would give."""
    cap = fields[0][0].shape[0]
    assert cap < (1 << 31)
    perm = jnp.arange(cap, dtype=jnp.int32)
    # least significant first: each run of bit fields is one loop of
    # word passes, each float64 field (nbits == 0) one pass of its own
    run: List = []
    for arr, nbits in reversed(fields):
        if nbits:
            run.insert(0, (arr, nbits))
            continue
        perm = _stable_pass(arr, _sort_by_words(_pack_words(run), perm))
        run = []
    perm = _sort_by_words(_pack_words(run), perm)
    return perm.astype(jnp.arange(0).dtype)
