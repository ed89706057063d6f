"""TPC-H Q9 as the benchmark's cell `tpch_q9` runs it, small, on the CPU:
the engine against the cell's plain reference on the cell's generator,
the generator's dbgen shape, and the spans the cell's two readers read
(`bodo:join.<route>`, one a join's realisation, and `bodo:strpred.lut`).
"""

import os
import sys

import numpy as np
import pytest

from bodo_tpu.config import config, set_config
from bodo_tpu.plan import fusion
from bodo_tpu.sql import BodoSQLContext
from bodo_tpu.utils import tracing

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import compare, spec  # noqa: E402

SEEDS = [1, 2147483777, 2200000001]
STRUCTURE = {"structure_seed": 20260926}


@pytest.fixture(scope="module")
def gen():
    return spec.load_module("gen", "tpch_parts")


@pytest.fixture(scope="module")
def query():
    return spec.Query("tpch_q9")


@pytest.fixture(autouse=True)
def _no_result_cache():
    old = config.result_cache
    set_config(result_cache=False)
    yield
    set_config(result_cache=old)


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("seed", SEEDS)
def test_engine_gives_the_reference_answer(gen, query, seed, mesh8):
    inputs = gen.generate({"orders": 3000, **STRUCTURE}, seed)
    got = BodoSQLContext(inputs["frames"]).sql(query.text).to_pandas()
    ref = query.reference().answer(inputs)
    assert 0 < len(ref) <= 175
    assert list(ref.columns) == ["nation", "o_year", "sum_profit"]
    gap = compare.answer_gap(got, ref)
    assert gap["columns_differ"] == gap["rows_differ"] == 0
    # every key cell, in the reference's order: nation up, year down
    assert gap["exact_cells_differ"] == 0
    assert gap["float_rel_gap"] <= 1e-10
    by_nation = ref.groupby("nation", sort=False)["o_year"]
    assert ref["nation"].is_monotonic_increasing
    assert all(by_nation.apply(lambda y: y.is_monotonic_decreasing))


# ---------------------------------------------------- the generator's shape
def test_partsupp_is_four_suppliers_a_part_and_every_line_finds_one(gen):
    f = gen.generate({"orders": 7500, **STRUCTURE}, 1)["frames"]
    part, ps, li = f["part"], f["partsupp"], f["lineitem"]
    assert len(part) == 1000 and len(ps) == 4 * len(part)
    assert (ps.groupby("ps_partkey").size() == 4).all()
    assert not ps.duplicated(["ps_partkey", "ps_suppkey"]).any()
    assert ps["ps_suppkey"].between(0, len(f["supplier"]) - 1).all()
    found = li.merge(ps, left_on=["l_partkey", "l_suppkey"],
                     right_on=["ps_partkey", "ps_suppkey"], how="left",
                     indicator=True)
    assert len(found) == len(li) and (found["_merge"] == "both").all()
    assert li["l_partkey"].between(0, len(part) - 1).all()


@pytest.mark.parametrize("orders", [40, 400, 30000])
def test_no_part_repeats_a_supplier_at_any_scale(gen, orders):
    f = gen.generate({"orders": orders, **STRUCTURE}, 1)["frames"]
    assert len(f["partsupp"]) == 4 * len(f["part"])
    assert not f["partsupp"].duplicated(["ps_partkey", "ps_suppkey"]).any()


def test_names_are_five_different_colours_and_green_is_5_in_92(gen):
    names = gen.part_names(np.random.default_rng(3), 30000)
    words = np.char.split(names)
    assert all(len(w) == 5 and len(set(w)) == 5 and set(w) <= set(gen.COLORS)
               for w in words)
    assert len(gen.COLORS) == len(set(gen.COLORS)) == 92
    share = np.mean(np.char.find(names, "green") >= 0)
    assert abs(share - 5 / 92) < 5 / 92 / 3
    # all but distinct: a dictionary as long as the table
    assert len(set(names)) > 0.99 * len(names)


def test_shapes_and_green_parts_do_not_depend_on_the_seed(gen):
    p = {"orders": 7500, **STRUCTURE}
    a, b = gen.generate(p, SEEDS[0]), gen.generate(p, SEEDS[1])
    assert a["rows"] == b["rows"]
    assert set(a["frames"]) == {"part", "supplier", "lineitem", "partsupp",
                                "orders", "nation"}
    fa, fb = a["frames"], b["frames"]
    # names, keys and foreign keys are structure; measures are the seed's
    assert fa["part"].equals(fb["part"])
    for c in ("l_orderkey", "l_partkey", "l_suppkey"):
        assert fa["lineitem"][c].equals(fb["lineitem"][c])
    for c in ("ps_partkey", "ps_suppkey"):
        assert fa["partsupp"][c].equals(fb["partsupp"][c])
    assert not fa["partsupp"]["ps_supplycost"].equals(
        fb["partsupp"]["ps_supplycost"])
    assert not fa["lineitem"]["l_extendedprice"].equals(
        fb["lineitem"]["l_extendedprice"])
    green = fa["part"]["p_name"].str.contains("green", regex=False)
    assert 0 < green.sum() < len(green)
    other = gen.generate({"orders": 7500, "structure_seed": 7}, SEEDS[0])
    assert not other["frames"]["part"]["p_name"].equals(fa["part"]["p_name"])


@pytest.mark.parametrize("seed", SEEDS)
def test_shared_columns_are_gen_tpch_s_own(gen, seed):
    p = {"orders": 7500, **STRUCTURE}
    ours = gen.generate(p, seed)["frames"]
    theirs = spec.load_module("gen", "tpch").generate(p, seed)["frames"]
    shared = 0
    for t in set(ours) & set(theirs):
        for c in set(ours[t].columns) & set(theirs[t].columns):
            if c == "l_suppkey":
                continue
            assert ours[t][c].equals(theirs[t][c]), (t, c)
            shared += 1
    assert shared == 10
    assert not ours["lineitem"]["l_suppkey"].equals(
        theirs["lineitem"]["l_suppkey"])
    # the columns Q9's file lists, and no others
    reads = spec.Query("tpch_q9").reads
    assert {t: sorted(df.columns) for t, df in ours.items()} \
        == {t: sorted(cols) for t, cols in reads.items()}


# ------------------------------------------------------------------- spans
def test_join_routes_and_the_like_s_table_reach_the_profiler(
        gen, query, tmp_path, mesh8):
    # names no test before this one has shown the engine: the program
    # that holds their table is keyed on the dictionary
    inputs = gen.generate({"orders": 3000, "structure_seed": 11}, SEEDS[0])
    names = inputs["frames"]["part"]["p_name"]
    ctx = BodoSQLContext(inputs["frames"])
    assert config.tracing_level == 0
    tracing.reset()
    before = fusion.stats()

    first, _ = profiled(tmp_path / "first",
                        lambda: ctx.sql(query.text).to_pandas())
    luts = [s for s in first if s[1] == "bodo:strpred.lut"]
    assert len(luts) == 1
    assert luts[0][4]["dict_size"] == names.nunique()
    assert luts[0][4]["kind"] == "contains"

    again, _ = profiled(tmp_path / "again",
                        lambda: ctx.sql(query.text).to_pandas())
    # the compiled program keeps the table: a repeat builds none
    assert not [s for s in again if s[1] == "bodo:strpred.lut"]

    for spans in (first, again):
        routes = [s for s in spans if s[1].startswith("bodo:join.")]
        assert {s[1] for s in routes} <= {
            "bodo:join.dense", "bodo:join.hash", "bodo:join.sort",
            "bodo:join.fused"}
        # Q9 has five joins (at this size a fused group's fallback and the
        # re-plan after it make the engine run four of them twice)
        assert len(routes) >= 5
        for s in routes:
            assert s[4]["keys"] >= 1 and s[4]["rows_left"] >= 0 \
                and s[4]["rows_right"] > 0
        # one realisation a join: every join_tables call that was not
        # nested in another operator's span holds exactly one route, and
        # a fused group's probe is the only route outside one
        calls = [s for s in spans if s[1] == "bodo:join_tables"]
        assert calls
        for _, _, a, b, _ in calls:
            assert sum(a <= s[2] and s[3] <= b for s in routes) == 1
        outside = [s for s in routes
                   if not any(a <= s[2] and s[3] <= b
                              for _, _, a, b, _ in calls)]
        assert all(s[1] == "bodo:join.fused" for s in outside)
        # the partsupp join is the one on the pair
        pairs = [s for s in routes if s[4]["keys"] == 2]
        assert pairs and all(
            s[4]["rows_right"] == len(inputs["frames"]["partsupp"])
            for s in pairs)
    after = fusion.stats()
    counted = sum(after[k] - before[k] for k in
                  ("join_dense", "join_hash", "join_sort", "join_fused"))
    assert counted == sum(s[1].startswith("bodo:join.")
                          for s in first + again)
    # with tracing off the spans went to the profiler alone
    assert not tracing.has_events()
