"""TPC-H Q9 as the benchmark's cell `tpch_q9_4chip` runs it, small, on a
mesh of four of the CPU's virtual devices, and the join that cell
hash-shuffles: two sharded sides of like size, each shuffled by
`shuffle_by_key` and joined by `_join_sharded`, on one key and on the
int64 pair, against `pandas.merge`; what each shuffle's span records
(`rows`, `rows_crossed`, `a2a_bytes`, `rows_max`, `rows_mean`), checked
against the engine's hash computed here; a key layout whose output
overflows the join's first bucket and takes the exact-count retry; and
one whose shards come out uneven, its skew read back by the benchmark's
reader `shuffle_skew` from the trace."""

import glob
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import bodo_tpu
from bodo_tpu import Table
from bodo_tpu.config import config, set_config
from bodo_tpu.ops.hashing import dest_shard, hash_columns
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan import fusion
from bodo_tpu import relational as R
from bodo_tpu.sql import BodoSQLContext
from bodo_tpu.table.table import round_capacity

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import compare, spec, trace  # noqa: E402

# 20,000 orders: lineitem 79,997 rows, part 2,666, partsupp 10,664; from
# 1,000 rows up a table shards, as from 100,000 up at the cell's scale
SMALL = {"orders": 20000, "structure_seed": 20260926}
SHARD_FROM = 1000
S = 4


@pytest.fixture
def mesh4(mesh8):
    """Four of the eight virtual devices, as the cell's four chips."""
    old = mesh_mod.get_mesh()
    bodo_tpu.set_mesh(bodo_tpu.make_mesh(jax.devices()[:S]))
    yield
    bodo_tpu.set_mesh(old)


@pytest.fixture(autouse=True)
def _engine_state():
    old = (config.result_cache, config.shard_min_rows)
    set_config(result_cache=False, shard_min_rows=SHARD_FROM)
    yield
    set_config(result_cache=old[0], shard_min_rows=old[1])


def _delta(before, key):
    return fusion.stats()[key] - before[key]


# ------------------------------------------------ the cell, small, on four
@pytest.mark.parametrize("seed", [1, 2147483777])
def test_engine_gives_the_reference_answer_on_four_devices(seed, mesh4):
    q = spec.Query("tpch_q9")
    inputs = spec.load_module("gen", "tpch_parts").generate(SMALL, seed)
    ctx = BodoSQLContext(inputs["frames"])
    before = fusion.stats()
    got = ctx.sql(q.text).to_pandas()
    ref = q.reference().answer(inputs)
    gap = compare.answer_gap(got, ref)
    assert 0 < len(ref) <= 175
    assert gap["columns_differ"] == gap["rows_differ"] == 0
    assert gap["exact_cells_differ"] == 0
    assert gap["float_rel_gap"] <= 1e-10
    # the join on the pair (partkey, suppkey) of the green parts'
    # lineitem rows with partsupp: neither side four times the other,
    # so the plan shuffles both by its own rules
    assert _delta(before, "exchange_shuffle") == 1


# ---------------------------------------------- the shuffled join alone
def _frame(n, space, seed, payload):
    r = np.random.default_rng(seed)
    f = pd.DataFrame({"k0": r.integers(0, space, n).astype(np.int64)})
    f["k1"] = (f["k0"] * 31 + 7) % 1013
    f[payload] = r.normal(size=n)
    return f


def _expected_shuffle(frame, on):
    """What `shuffle_by_key` of `frame`, scattered over the four
    devices, has to record: rows a shard keeps are those hashed to it."""
    n = len(frame)
    per = round_capacity(-(-n // S))
    keys = tuple((jnp.asarray(frame[k].to_numpy()), None) for k in on)
    dest = np.asarray(dest_shard(hash_columns(keys), S))
    held = np.bincount(dest, minlength=S)
    row_bytes = sum(frame[c].dtype.itemsize for c in frame.columns)
    return {"rows": n, "rows_crossed": int(np.sum(dest != np.arange(n)
                                                  // per)),
            "a2a_bytes": S * S * per * row_bytes,
            "rows_max": int(held.max()), "rows_mean": n / S}


def _join_traced(tmp_path, left, right, on):
    """`join_tables` of the two frames scattered over the mesh, under a
    profiler session whose window is the join; the answer, the trace's
    reduction, and the arguments of the shuffle spans."""
    lt, rt = Table.from_pandas(left).shard(), Table.from_pandas(right).shard()
    out = {}

    def join():
        with jax.profiler.TraceAnnotation("bench:window"):
            out["t"] = R.join_tables(lt, rt, on, on, "inner").to_pandas()
    spans, _ = profiled(tmp_path, join)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    shuffles = [s[4] for s in sorted(spans, key=lambda s: s[2])
                if s[1] == "bodo:shuffle_by_key"]
    return out["t"], trace.Reduction(path), shuffles


def _against_merge(got, left, right, on):
    want = left.merge(right, on=on)
    cols = list(want.columns)
    pd.testing.assert_frame_equal(
        got[cols].sort_values(cols).reset_index(drop=True),
        want.sort_values(cols).reset_index(drop=True))


@pytest.mark.parametrize("keys", [1, 2], ids=["one_key", "int64_pair"])
def test_shuffled_join_records_what_it_moved(keys, mesh4, tmp_path):
    on = ["k0", "k1"][:keys]
    left = _frame(6000, 20000, 1, "v")
    right = _frame(5000, 20000, 2, "w")
    before = fusion.stats()
    got, _, shuffles = _join_traced(tmp_path, left, right, on)
    _against_merge(got, left, right, on)
    assert _delta(before, "exchange_shuffle") == 1
    assert _delta(before, "join_overflow_retries") == 0
    assert len(shuffles) == 2
    for args, frame in zip(shuffles, (left, right)):
        want = _expected_shuffle(frame, on)
        assert {k: args[k] for k in want} == pytest.approx(want)
        # the keys first, then the rest, under the names the join gave
        assert args["columns"].split() == on + [c for c in frame.columns
                                                if c not in on]


def test_output_overflow_takes_the_exact_count_retry(mesh4, tmp_path):
    # 40 rows a side a pair over 50 pairs: 80,000 rows out of 2,000 x
    # 2,000, where the first bucket holds twice a shard's 512 probe rows
    on = ["k0", "k1"]
    left = _frame(2000, 50, 3, "v")
    right = _frame(2000, 50, 4, "w")
    before = fusion.stats()
    got, _, shuffles = _join_traced(tmp_path, left, right, on)
    _against_merge(got, left, right, on)
    assert len(got) == len(left.merge(right, on=on)) > 4 * 2 * 512
    assert _delta(before, "exchange_shuffle") == 1
    assert _delta(before, "join_overflow_retries") == 1


def test_uneven_shards_record_their_skew(mesh4, tmp_path):
    # three rows in four of the probe side on one pair: its shard holds
    # them all after the shuffle
    on = ["k0", "k1"]
    left = _frame(4000, 20000, 5, "v")
    hot = np.random.default_rng(6).random(len(left)) < 0.75
    left.loc[hot, ["k0", "k1"]] = [17, (17 * 31 + 7) % 1013]
    right = _frame(4000, 20000, 7, "w")
    got, reduction, shuffles = _join_traced(tmp_path, left, right, on)
    _against_merge(got, left, right, on)
    want = _expected_shuffle(left, on)
    assert shuffles[0]["rows_max"] == want["rows_max"] >= 0.75 * len(left)
    skew = want["rows_max"] / want["rows_mean"]
    assert skew > 2.9
    reader = spec.load_module("layer_metrics", "shuffle_skew")
    run = types.SimpleNamespace(trace=reduction, traced_queries=1)
    assert reader.read(run) == pytest.approx(skew)
