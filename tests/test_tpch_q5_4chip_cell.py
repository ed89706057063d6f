"""TPC-H Q5 as the benchmark's cell `tpch_q5_4chip` runs it, small, on a
mesh of four of the CPU's virtual devices: the engine against the
cell's plain reference on the cell's generator with `customer`, `orders`
and `lineitem` row-sharded; each way a join's rows cross chips, alone,
against `pandas.merge`, with the route it takes (a replicated build
under a sharded probe is a LUT probed once a shard, `probe="sharded"`);
the spans and counters that say which way was taken
(`bodo:exchange.shuffle`, `bodo:exchange.broadcast`,
`exchange_inprogram`, `join_lut_sharded`); and a registered table
scattered once, not once a query."""

import json
import os
import sys

import jax
import numpy as np
import pandas as pd
import pytest

import bodo_tpu
from bodo_tpu import Table
from bodo_tpu import relational as R
from bodo_tpu.config import config, set_config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.table.table import Column, ONED, round_capacity
from bodo_tpu.plan import fusion
from bodo_tpu.sql import BodoSQLContext
from bodo_tpu.utils import tracing

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import compare, spec  # noqa: E402

SEEDS = [1, 2147483777, 2200000001]
# 20,000 orders: customer 2,000, lineitem 79,997 rows; from 1,000 rows up
# a table shards, as from 100,000 up at the cell's scale
SMALL = {"orders": 20000, "structure_seed": 20260926}
SHARD_FROM = 1000


@pytest.fixture(scope="module")
def gen():
    return spec.load_module("gen", "tpch")


@pytest.fixture(scope="module")
def query():
    return spec.Query("tpch_q5")


@pytest.fixture
def mesh4(mesh8):
    """Four of the eight virtual devices, as the cell's four chips."""
    old = mesh_mod.get_mesh()
    bodo_tpu.set_mesh(bodo_tpu.make_mesh(jax.devices()[:4]))
    yield
    bodo_tpu.set_mesh(old)


@pytest.fixture(autouse=True)
def _engine_state():
    old = (config.result_cache, config.shard_min_rows,
           config.bcast_join_threshold)
    set_config(result_cache=False, shard_min_rows=SHARD_FROM)
    yield
    set_config(result_cache=old[0], shard_min_rows=old[1],
               bcast_join_threshold=old[2])


def exchanges(before, after):
    return {k[len("exchange_"):]: after[k] - before[k]
            for k in after if k.startswith("exchange_")}


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("seed", SEEDS)
def test_engine_gives_the_reference_answer_on_four_devices(gen, query, seed,
                                                           mesh4):
    inputs = gen.generate(SMALL, seed)
    ctx = BodoSQLContext(inputs["frames"])
    before = fusion.stats()
    got = ctx.sql(query.text).to_pandas()
    moved = exchanges(before, fusion.stats())
    ref = query.reference().answer(inputs)
    luts = fusion.stats()["join_lut_sharded"] - before["join_lut_sharded"]
    assert len(ref) == 5
    gap = compare.answer_gap(got, ref)
    assert gap["columns_differ"] == gap["rows_differ"] == 0
    assert gap["exact_cells_differ"] == 0
    assert gap["float_rel_gap"] <= 1e-10
    # at this scale the plan holds a join of two sharded sides of like
    # size (a hash shuffle of both) and a build side that is replicated
    assert moved["shuffle"] >= 1 and moved["broadcast"] >= 1, moved
    # and the replicated build under sharded `lineitem` is a LUT probed
    # once a shard
    assert luts >= 1


def test_a_registered_table_is_scattered_once(gen, query, mesh4, tmp_path):
    """`lineitem` holds columns Q5 does not read, so the optimizer cuts a
    new source node for every query; the columns sharded for the mesh
    are kept on the registered node, and a second query scatters
    nothing."""
    inputs = gen.generate(SMALL, 1)
    ctx = BodoSQLContext(inputs["frames"])
    ctx.sql(query.text).to_pandas()
    held = ctx._tables["lineitem"].placed
    assert sorted(held["columns"]) == ["l_discount", "l_extendedprice",
                                       "l_orderkey", "l_suppkey"]
    first = {n: c[1].data for n, c in held["columns"].items()}
    spans, _ = profiled(tmp_path, lambda: ctx.sql(query.text).to_pandas())
    assert "bodo:dist.shard" not in {s[1] for s in spans}
    assert all(held["columns"][n][1].data is d for n, d in first.items())
    # another mesh: the columns are scattered for it, from the
    # replicated originals the node still holds
    from bodo_tpu.plan import physical
    node = ctx._tables["lineitem"]
    bodo_tpu.set_mesh(bodo_tpu.make_mesh(jax.devices()[:2]))
    t = physical._place_source(node)
    assert t.num_shards == 2 and held["mesh"] == mesh_mod.get_mesh()
    assert sorted(held["columns"]) == sorted(node.table.names)
    pd.testing.assert_frame_equal(t.to_pandas(), inputs["frames"]["lineitem"])


# ------------------------------------------ each realisation against pandas
def _sides(n_left, n_right, keys, seed=0, key_space=None):
    """Two frames joined on `k0` (and `k1`, a function of it): the side
    of fewer rows is the dimension, its keys unique, the other's drawn
    with repeats from the same space."""
    r = np.random.default_rng(seed)
    space = key_space or n_right

    def frame(n, unique, payload):
        f = pd.DataFrame({"k0": (r.permutation(space)[:n] if unique
                                 else r.integers(0, space, n))
                          .astype(np.int64)})
        f["k1"] = f["k0"] % 7
        f[payload] = r.normal(size=n)
        return f
    right = frame(n_right, n_right <= n_left, "w")
    left = frame(n_left, n_right > n_left, "v")
    return left, right, ["k0", "k1"][:keys]


ROUTES = ("join_dense", "join_hash", "join_sort", "join_lut_sharded",
          "join_emit", "join_emit_skipped")


def _masked(frame, on):
    """`frame` as a LUT join's build side comes back: every column a
    LUT gathers carries a mask, as on one chip and in a fused join
    group, so an int64 one reads as Int64."""
    return frame.astype({c: "Int64" for c in frame.columns
                         if c not in on and frame[c].dtype == np.int64})


def _joined(left, right, on, left_t, right_t, how="inner",
            null_equal=True, lut_on=None):
    """`join_tables` of the two tables against `pandas.merge` of the
    frames they hold, dtypes too; what moved, and the routes the join
    took. `lut_on` names the side a LUT is built on ("left", "right")."""
    before = fusion.stats()
    out = R.join_tables(left_t, right_t, on, on, how,
                        null_equal=null_equal).to_pandas()
    after = fusion.stats()
    if lut_on == "left":
        left = _masked(left, on)
    elif lut_on == "right":
        right = _masked(right, on)
    if null_equal:
        want = left.merge(right, on=on, how=how)
    else:   # SQL: a null key matches nothing
        want = left.merge(right.dropna(subset=on), on=on, how=how)
        if how == "inner":
            want = want.dropna(subset=on)
    cols = list(want.columns)
    pd.testing.assert_frame_equal(
        out[cols].sort_values(cols).reset_index(drop=True),
        want.sort_values(cols).reset_index(drop=True))
    return exchanges(before, after), \
        {k: after[k] - before[k] for k in ROUTES}


def _tables(left, right, shard_left, shard_right):
    lt, rt = Table.from_pandas(left), Table.from_pandas(right)
    return (lt.shard() if shard_left else lt,
            rt.shard() if shard_right else rt)


# (case, rows left, rows right, keys, left sharded, right sharded,
#  shuffles, broadcasts, route): both sides sharded and alike in size
# shuffle, on one key and on a pair, and sort; a small sharded build is
# gathered; a replicated build under a sharded probe is read as it
# stands; a small replicated LEFT under a sharded right is the build
# without a scatter: the three are a dense LUT probed once a shard
REALISATIONS = [
    ("shuffle_one_key", 6000, 5000, 1, True, True, 1, 0, "join_sort"),
    ("shuffle_pair", 6000, 5000, 2, True, True, 1, 0, "join_sort"),
    ("gather_small_sharded_build", 9000, 900, 1, True, True, 0, 1,
     "join_dense"),
    ("replicated_build", 9000, 900, 1, True, False, 0, 1, "join_dense"),
    ("replicated_left_is_the_build", 900, 9000, 1, False, True, 0, 1,
     "join_dense"),
]


@pytest.mark.parametrize("case", REALISATIONS, ids=[c[0] for c in
                                                    REALISATIONS])
def test_realisation_alone_against_pandas_merge(case, mesh4):
    _, nl, nr, keys, sl, sr, shuffles, broadcasts, route = case
    left, right, on = _sides(nl, nr, keys, key_space=max(nl, nr))
    lut_on = None if route == "join_sort" else \
        ("left" if nl < nr else "right")
    moved, took = _joined(left, right, on, *_tables(left, right, sl, sr),
                          lut_on=lut_on)
    assert moved["shuffle"] == shuffles, moved
    assert moved["broadcast"] == broadcasts, moved
    assert took[route] == 1, took
    assert took["join_sort"] == (route == "join_sort"), took
    assert took["join_lut_sharded"] == (route != "join_sort"), took


def _dealt(frame, counts):
    """`frame`'s rows dealt to the four shards in runs of `counts` (a
    shard may hold none), as a row-sharded table."""
    per = round_capacity(max(counts))
    slots = np.concatenate([s * per + np.arange(c)
                            for s, c in enumerate(counts)])
    t = Table.from_pandas(frame)
    rows = mesh_mod.row_sharding(mesh_mod.get_mesh())

    def place(a, n):
        out = np.zeros(4 * per, np.asarray(a).dtype)
        out[slots] = np.asarray(a)[:n]
        return jax.device_put(out, rows)
    cols = {n: Column(place(c.data, len(frame)),
                      None if c.valid is None else place(c.valid,
                                                         len(frame)),
                      c.dtype, c.dictionary, c.vrange)
            for n, c in t.columns.items()}
    return Table(cols, len(frame), ONED,
                 np.asarray(counts, dtype=np.int64))


def _with_null_keys(frame, share, seed):
    """`frame` with a nullable key `k0` null in about `share` of rows
    (at least one)."""
    out = frame.copy()
    out["k0"] = out["k0"].astype("Int64")
    nulls = np.random.default_rng(seed).random(len(out)) < share
    nulls[0] = True
    out.loc[nulls, "k0"] = pd.NA
    return out


def _sharded_probe_case(case):
    """(left frame, right frame, on, left table, right table, how,
    null_equal) of a sharded probe over a replicated build."""
    on = ["k0"]
    if case == "left_join":
        left, right, _ = _sides(9000, 900, 1, key_space=9000)
        return (left, right, on, *_tables(left, right, True, False),
                "left", True)
    if case == "build_keys_repeat":
        # 900 rows over 100 key values: no LUT, before anything is built
        left, right, _ = _sides(9000, 900, 1, key_space=9000)
        right["k0"] = right["k0"] % 100
        left["k0"] = left["k0"] % 150
    elif case == "build_a_key_twice":
        # unique but for one key, found by the builds themselves
        left, right, _ = _sides(9000, 900, 1, key_space=9000)
        right.loc[1, "k0"] = right.loc[0, "k0"]
    elif case == "build_too_sparse_for_dense":
        left, right, _ = _sides(9000, 900, 1, key_space=9000)
        right["k0"] = right["k0"] * 100_003
        left["k0"] = np.where(np.arange(9000) % 2 == 0,
                              right["k0"].to_numpy()[np.arange(9000) % 900],
                              left["k0"] * 100_003 + 1)
    elif case.startswith("null_probe_keys"):
        left, right, _ = _sides(9000, 900, 1, key_space=9000)
        left = _with_null_keys(left, 0.1, 5)
        right = _with_null_keys(right, 0.0, 6)   # one null build key
        return (left, right, on, *_tables(left, right, True, False),
                "inner", case == "null_probe_keys_null_equal")
    elif case == "all_hit":
        left, right, _ = _sides(9000, 900, 1, key_space=900)
    elif case == "small_lut_pallas_armed":
        # 3,000 slots, under the one-hot gather's `MAX_MATMUL_SLOTS`
        left, right, _ = _sides(9000, 900, 1, key_space=3000)
    elif case == "unequal_shards_one_empty":
        left, right, _ = _sides(5000, 900, 1, key_space=9000)
        return (left, right, on, _dealt(left, [3000, 0, 1500, 500]),
                Table.from_pandas(right), "inner", True)
    return (left, right, on, *_tables(left, right, True, False), "inner",
            True)


# (case, the route it takes, emitted, emit skipped): each a sharded probe
# over a replicated build, against `pandas.merge`
SHARDED_PROBE = [
    ("left_join", "join_dense", 0, 0),
    ("build_keys_repeat", "join_sort", 0, 0),
    ("build_a_key_twice", "join_sort", 0, 0),
    ("build_too_sparse_for_dense", "join_hash", 1, 0),
    # one null key on each side: SQL's nulls match nothing, so the dense
    # LUT drops them; pandas' match each other, which the hash LUT codes
    ("null_probe_keys_null_not_equal", "join_dense", 1, 0),
    ("null_probe_keys_null_equal", "join_hash", 1, 0),
    ("all_hit", "join_dense", 0, 1),
    ("unequal_shards_one_empty", "join_dense", 1, 0),
    # with the Pallas kernels armed (interpret mode), as on the chip
    ("small_lut_pallas_armed", "join_dense", 1, 0),
]


@pytest.mark.parametrize("case", SHARDED_PROBE,
                         ids=[c[0] for c in SHARDED_PROBE])
def test_sharded_probe_against_pandas_merge(case, mesh4):
    from bodo_tpu.ops import pallas_kernels as PK
    name, route, emits, skips = case
    left, right, on, lt, rt, how, null_equal = _sharded_probe_case(name)
    old, gathers = PK.FORCE_INTERPRET, PK.trace_counts["gather"]
    PK.FORCE_INTERPRET = name == "small_lut_pallas_armed"
    lut = route != "join_sort"
    try:
        moved, took = _joined(left, right, on, lt, rt, how, null_equal,
                              lut_on="right" if lut else None)
    finally:
        PK.FORCE_INTERPRET = old
    # a sharded probe keeps XLA's gather where a replicated one would
    # take the one-hot kernel
    assert PK.trace_counts["gather"] == gathers
    assert moved["broadcast"] == 1 and moved["shuffle"] == 0, moved
    assert took[route] == 1, took
    assert took["join_sort"] == (not lut), took
    assert took["join_lut_sharded"] == lut, took
    assert (took["join_emit"], took["join_emit_skipped"]) == \
        (emits, skips), took


def test_sharded_probe_keeps_its_shards(mesh4):
    """The result of a LUT join probed by a sharded side stays sharded,
    each shard's rows in place: a shard's result is its own probe rows'
    matches, counted once on the host."""
    left, right, _ = _sides(5000, 900, 1, key_space=9000)
    counts = [3000, 0, 1500, 500]
    out = R.join_tables(_dealt(left, counts), Table.from_pandas(right),
                        ["k0"], ["k0"], "inner")
    assert out.distribution == ONED
    hits = left["k0"].isin(right["k0"]).to_numpy()
    bounds = np.cumsum([0] + counts)
    assert list(out.counts) == [int(hits[a:b].sum())
                                for a, b in zip(bounds, bounds[1:])]


def test_replicated_left_is_not_scattered_to_be_gathered(mesh4, tmp_path):
    left, right, on = _sides(900, 9000, 1, key_space=9000)
    lt, rt = Table.from_pandas(left), Table.from_pandas(right).shard()
    spans, _ = profiled(
        tmp_path, lambda: R.join_tables(lt, rt, on, on, "inner"))
    names = [s[1] for s in spans]
    assert "bodo:dist.shard" not in names
    assert "bodo:dist.gather" not in names
    assert names.count("bodo:exchange.broadcast") == 1


# ------------------------------------- Q5's `order by revenue desc`, sharded
@pytest.mark.parametrize("ascending", [True, False])
def test_sharded_sort_on_a_float64_key(ascending, mesh4):
    """The sample sort partitions by the key rounded to float32 (the
    v5e cannot bitcast a float64) and sorts each shard by the float64
    itself: values that round alike (all of them, here, but the signs)
    stay in order, nulls last."""
    r = np.random.default_rng(7)
    v = 1e9 + r.permutation(4000) * 1e-3
    v[::5] *= -1.0
    v[7::50] = np.nan
    assert len(np.unique(v[~np.isnan(v)].astype(np.float32))) < 10
    df = pd.DataFrame({"revenue": v, "i": np.arange(4000)})
    out = R.sort_table(Table.from_pandas(df).shard(), ["revenue"],
                       ascending=[ascending]).to_pandas()
    want = df.sort_values("revenue", ascending=ascending,
                          kind="stable").reset_index(drop=True)
    np.testing.assert_array_equal(out["revenue"].to_numpy(),
                                  want["revenue"].to_numpy())
    assert sorted(out["i"]) == list(range(4000))


# ------------------------------------------------ spans under the profiler
def test_exchange_spans_carry_their_arguments(gen, query, mesh4, tmp_path):
    # the ring buffer is the process's: a test before this one on the
    # same worker may have left events in it
    tracing.reset()
    inputs = gen.generate(SMALL, 1)
    ctx = BodoSQLContext(inputs["frames"])
    ctx.sql(query.text).to_pandas()            # compile, place the tables
    assert config.tracing_level == 0
    spans, modules = profiled(tmp_path,
                              lambda: ctx.sql(query.text).to_pandas())
    by_name = {}
    for s in spans:
        by_name.setdefault(s[1], []).append(s)
    shuffle = by_name["bodo:exchange.shuffle"][0]
    assert shuffle[4]["keys"] == 1
    assert shuffle[4]["rows_left"] > 0 and shuffle[4]["rows_right"] > 0
    # the two shuffles of the join lie inside the span, the span inside
    # the route's
    inside = [s for s in by_name["bodo:shuffle_by_key"]
              if shuffle[2] <= s[2] and s[3] <= shuffle[3]]
    assert len(inside) == 2
    assert any(j[2] <= shuffle[2] and shuffle[3] <= j[3]
               for j in by_name["bodo:join.sort"])
    gathers = [b for b in by_name["bodo:exchange.broadcast"]
               if any(b[2] <= g[2] and g[3] <= b[3]
                      for g in by_name.get("bodo:dist.gather", []))]
    assert gathers and all(b[4]["rows"] > 0 for b in gathers)
    # the replicated build under sharded `lineitem` is a LUT probed once
    # a shard, under the programs' own names
    assert any(s[4].get("probe") == "sharded"
               for s in by_name["bodo:join.dense"]
               + by_name.get("bodo:join.hash", []))
    assert "jit_shuffle_by_key" in modules
    assert "jit_join_sharded" in modules
    assert "jit_join_probe_dense" in modules
    # the untraced contract held: nothing in the ring buffer
    assert not tracing.has_events()


def test_fused_group_counts_its_in_program_gather(mesh4):
    """A fused join group whose sharded build is too large to broadcast
    gathers it inside its program: no host span, a counter and an
    argument on the group's span."""
    set_config(bcast_join_threshold=100)
    r = np.random.default_rng(3)
    fact = pd.DataFrame({"k": r.integers(0, 3000, 12000).astype(np.int64),
                         "v": r.normal(size=12000)})
    dim = pd.DataFrame({"k": np.arange(3000, dtype=np.int64),
                        "g": np.arange(3000, dtype=np.int64) % 5})
    ctx = BodoSQLContext({"fact": fact, "dim": dim})
    sql = ("select d.g, sum(f.v) as s from fact f join dim d "
           "on f.k = d.k group by d.g order by d.g")
    before = fusion.stats()
    old = config.tracing_level
    set_config(tracing_level=1)
    tracing.reset()
    try:
        got = ctx.sql(sql).to_pandas()
        groups = [e for e in json.loads(tracing.dump())["traceEvents"]
                  if e["name"] == "fused_join_group"]
    finally:
        set_config(tracing_level=old)
        tracing.reset()
    after = fusion.stats()
    want = fact.merge(dim, on="k").groupby("g", as_index=False)["v"].sum()
    np.testing.assert_allclose(got["s"].to_numpy(), want["v"].to_numpy())
    assert after["exchange_inprogram"] == before["exchange_inprogram"] + 1
    assert after["join_fused"] == before["join_fused"] + 1
    assert groups and groups[-1]["args"]["exchange"] == "inprogram"
    assert groups[-1]["args"]["build_rows"] == 3000
