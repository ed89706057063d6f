"""`ops/kernels.py compact` / `compact_index` against a NumPy reference:
the contract every caller leans on (stable, rows past the count zeroed,
`None` passed through, trailing dimensions kept, `capacity_out`
honoured) whichever realisation the function's rule picks for the
arrays it is handed."""

import numpy as np
import pytest

import bodo_tpu  # noqa: F401 - x64 on, as the engine runs
from bodo_tpu.ops import kernels as K

CAP = 1000


def _mask(kind, rng):
    if kind == "none":
        return np.zeros(CAP, bool)
    if kind == "all":
        return np.ones(CAP, bool)
    if kind == "prefix":
        return np.arange(CAP) < 137
    return rng.random(CAP) < 0.3        # scattered


def _arrays(spec, rng):
    make = {
        "f64": lambda: rng.normal(size=CAP),
        "i64": lambda: rng.integers(-1 << 40, 1 << 40, CAP),
        "i32": lambda: rng.integers(-100, 100, CAP).astype(np.int32),
        "bool": lambda: rng.random(CAP) < 0.5,
        "mat": lambda: rng.normal(size=(CAP, 3)),
        "None": lambda: None,
    }
    return tuple(make[s]() for s in spec)


def _reference(mask, arrays, out_cap):
    keep = np.flatnonzero(mask)[:out_cap]
    outs = []
    for a in arrays:
        if a is None:
            outs.append(None)
            continue
        o = np.zeros((out_cap,) + a.shape[1:], a.dtype)
        o[:len(keep)] = a[keep]
        outs.append(o)
    return outs, int(mask.sum())


CASES = [
    # (mask kind, arrays, capacity_out: None, "fit", or rows against count)
    ("scattered", (), None),
    ("scattered", ("f64",), None),
    ("scattered", ("i64", "f64"), None),
    ("scattered", ("f64", "i64", "i32", "bool", "f64", "i64", "bool"), None),
    ("scattered", ("f64", "None", "i64", "None", "bool"), None),
    ("scattered", ("mat",), None),
    ("scattered", ("mat", "f64", "i64", "bool", "i32"), None),
    ("none", ("f64", "i64", "bool"), None),
    ("none", ("f64",), 128),
    ("all", ("f64", "i64", "bool", "i32", "f64"), None),
    ("all", ("i64",), None),
    ("prefix", ("f64", "i64", "bool", "i32"), None),
    ("prefix", ("f64", "i64"), 137),                 # equal to the count
    ("prefix", ("f64", "i64", "bool", "i32", "mat"), 256),    # larger
    ("scattered", ("f64", "i64", "bool", "i32", "f64"), 2048),  # > cap
    ("scattered", ("f64", "i64", "bool", "i32", "f64"), 100),  # smaller
    ("scattered", ("i64",), 100),                    # smaller, one array
    ("all", ("f64", "i64", "i32"), 64),              # smaller than the count
    # a lone array of one 32-bit word or less: the same one form
    ("scattered", ("i32",), None),
    ("prefix", ("None", "bool"), 100),
]


@pytest.mark.parametrize("kind,spec,out_cap", CASES)
def test_compact_matches_numpy(kind, spec, out_cap):
    import jax
    rng = np.random.default_rng(len(spec) * 7 + (out_cap or 0))
    mask = _mask(kind, rng)
    arrays = _arrays(spec, rng)
    want, count = _reference(mask, arrays, out_cap or CAP)
    fn = jax.jit(lambda m, a: K.compact(m, a, out_cap))
    got, n = fn(mask, arrays)
    assert int(n) == count
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kind", ["none", "all", "prefix", "scattered"])
@pytest.mark.parametrize("out_cap", [None, 64, 512, 4096])
def test_compact_index_ascending_and_stable(kind, out_cap):
    import jax
    rng = np.random.default_rng(3)
    mask = _mask(kind, rng)
    src, n = jax.jit(lambda m: K.compact_index(m, out_cap))(mask)
    src = np.asarray(src)
    keep = np.flatnonzero(mask)
    assert int(n) == len(keep)
    assert src.dtype == np.int32 and src.shape == (out_cap or CAP,)
    live = min(len(keep), len(src))
    np.testing.assert_array_equal(src[:live], keep[:live])
    # past the count the index is in range, so a gather needs no guard
    assert ((src >= 0) & (src < CAP)).all()
