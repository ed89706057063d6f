"""TPC-H Q5 as the benchmark's cell `tpch_q5_4chip` runs it, small, on a
mesh of four of the CPU's virtual devices: the engine against the
cell's plain reference on the cell's generator with `customer`, `orders`
and `lineitem` row-sharded; each way a join's rows cross chips, alone,
against `pandas.merge`; the spans and counters that say which way was
taken (`bodo:exchange.shuffle`, `bodo:exchange.broadcast`,
`exchange_inprogram`); and a registered table scattered once, not once
a query."""

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
    assert len(ref) == 5
    gap = compare.answer_gap(got, ref)
    assert gap["columns_differ"] == gap["rows_differ"] == 0
    assert gap["exact_cells_differ"] == 0
    assert gap["float_rel_gap"] <= 1e-10
    # at this scale the plan holds a join of two sharded sides of like
    # size (a hash shuffle of both) and a build side that is replicated
    assert moved["shuffle"] >= 1 and moved["broadcast"] >= 1, moved


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
    r = np.random.default_rng(seed)
    space = key_space or n_right
    right = pd.DataFrame({"k0": r.permutation(space)[:n_right]
                          .astype(np.int64)})
    right["k1"] = right["k0"] % 7
    right["w"] = r.normal(size=n_right)
    left = pd.DataFrame({"k0": r.integers(0, space, n_left)
                         .astype(np.int64)})
    left["k1"] = left["k0"] % 7
    left["v"] = r.normal(size=n_left)
    return left, right, ["k0", "k1"][:keys]


def _joined(left, right, on, shard_left, shard_right):
    lt, rt = Table.from_pandas(left), Table.from_pandas(right)
    if shard_left:
        lt = lt.shard()
    if shard_right:
        rt = rt.shard()
    before = fusion.stats()
    out = R.join_tables(lt, rt, on, on, "inner").to_pandas()
    after = fusion.stats()
    want = left.merge(right, on=on, how="inner")
    cols = list(want.columns)
    pd.testing.assert_frame_equal(
        out[cols].sort_values(cols).reset_index(drop=True),
        want.sort_values(cols).reset_index(drop=True))
    return exchanges(before, after), \
        after["join_sort"] - before["join_sort"]


# (case, rows left, rows right, keys, left sharded, right sharded,
#  shuffles, broadcasts): both sides sharded and alike in size shuffle,
# on one key and on a pair; a small sharded build is gathered; a
# replicated build under a sharded probe is read as it stands; a small
# replicated LEFT under a sharded right is the build without a scatter
REALISATIONS = [
    ("shuffle_one_key", 6000, 5000, 1, True, True, 1, 0),
    ("shuffle_pair", 6000, 5000, 2, True, True, 1, 0),
    ("gather_small_sharded_build", 9000, 900, 1, True, True, 0, 1),
    ("replicated_build", 9000, 900, 1, True, False, 0, 1),
    ("replicated_left_is_the_build", 900, 9000, 1, False, True, 0, 1),
]


@pytest.mark.parametrize("case", REALISATIONS, ids=[c[0] for c in
                                                    REALISATIONS])
def test_realisation_alone_against_pandas_merge(case, mesh4):
    _, nl, nr, keys, sl, sr, shuffles, broadcasts = case
    left, right, on = _sides(nl, nr, keys, key_space=max(nl, nr))
    moved, sorts = _joined(left, right, on, sl, sr)
    assert moved["shuffle"] == shuffles, moved
    assert moved["broadcast"] == broadcasts, moved
    assert sorts == 1


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
    assert "jit_shuffle_by_key" in modules
    assert "jit_join_sharded" in modules
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
