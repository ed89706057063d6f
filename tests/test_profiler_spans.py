"""The engine's spans and program names as a `jax.profiler` trace holds
them: `tracing.event()` mirrors every span into a TraceAnnotation
`bodo:<name>` whatever `tracing_level` is, and every program the engine
jits is named for its operator (`utils/kernel_cache.py named_jit`).
The benchmark's per-layer readers (`benchmarks/layer_metrics/`) read
both; these tests are their contract with the engine, on the CPU.
"""

import glob
import os
import re

import numpy as np
import pandas as pd
import pytest

import bodo_tpu
import jax
from bodo_tpu.config import config, set_config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.utils import tracing
from bodo_tpu.workloads import taxi as taxi_wl
from bodo_tpu.workloads.tpch import QUERIES, gen_tpch

GENERIC = re.compile(
    r"^jit_(body|fused|sharded|rep|fn|bbody|pbody)$")


@pytest.fixture(autouse=True)
def _engine_state():
    """Tiny inputs have to take the routes the cells take: the device
    decode of parquet pages, and no answer from the result cache."""
    old = (config.device_decode_min_bytes, config.result_cache,
           config.shard_min_rows, config.tracing_level)
    set_config(device_decode_min_bytes=0, result_cache=False)
    tracing.reset()
    yield
    set_config(device_decode_min_bytes=old[0], result_cache=old[1],
               shard_min_rows=old[2], tracing_level=old[3])
    tracing.reset()


def profiled(tmp_path, fn):
    """Run `fn` under a profiler session with the Python tracer off, as
    the benchmark's traced runs do; return (host spans, module names):
    spans as (thread line, name, start_ns, end_ns, stats), modules from
    the `hlo_module` stat of the CPU backend's operation events."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name.startswith("tf_XLA"):
                    mod = dict(ev.stats).get("hlo_module")
                    if mod is not None:
                        modules.add(re.sub(r"\(\d+\)$", "", mod))
                elif ev.name.startswith("bodo:"):
                    spans.append((line.name, ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return spans, modules


def taxi_files(tmp_path, rows=20000):
    trips, weather = str(tmp_path / "trips.pq"), str(tmp_path / "w.csv")
    taxi_wl.gen_taxi_data(rows, trips, weather, seed=3)
    return trips, weather


def small_join_sql():
    rng = np.random.default_rng(0)
    fact = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                         "v": rng.normal(size=4000)})
    dim = pd.DataFrame({"k": np.arange(50), "g": np.arange(50) % 5})
    ctx = bodo_tpu.sql.BodoSQLContext({"fact": fact, "dim": dim})
    return ctx, ("select d.g, sum(f.v) as s from fact f join dim d "
                 "on f.k = d.k group by d.g order by d.g")


# ------------------------------------------------------------------ spans
def test_spans_reach_the_profiler_with_tracing_off(tmp_path, mesh8):
    import bodo_tpu.pandas_api as bd
    assert config.tracing_level == 0
    trips, _ = taxi_files(tmp_path)
    ctx, sql = small_join_sql()

    def queries():
        t = bd.read_parquet(trips)
        t.groupby("PULocationID", as_index=False).agg(
            n=("trip_miles", "count")).to_pandas()
        ctx.sql(sql).to_pandas()

    spans, _ = profiled(tmp_path / "trace", queries)
    names = {s[1] for s in spans}
    for want in ("bodo:query", "bodo:plan.sql", "bodo:plan.optimize",
                 "bodo:scan.fetch", "bodo:scan.split", "bodo:scan.column",
                 "bodo:to_pandas", "bodo:result.d2h", "bodo:result.frame",
                 "bodo:Aggregate"):
        assert want in names, (want, sorted(names))
    # the untraced contract held all the while: nothing in the ring buffer
    assert not tracing.has_events() and tracing.query_agg() == {}
    # arguments ride as TraceMe metadata
    col = next(s for s in spans if s[1] == "bodo:scan.column")
    assert col[4]["pages"] >= 1 and col[4]["bytes"] > 0
    assert next(s for s in spans if s[1] == "bodo:to_pandas")[4]["rows"] > 0
    # parentage is nesting on the thread: on the query's own thread every
    # scan span and every operator's span lies inside a bodo:query
    main = max({s[0] for s in spans},
               key=lambda ln: sum(s[0] == ln and s[1] == "bodo:query"
                                  for s in spans))
    queries_ = [(a, b) for ln, n, a, b, _ in spans
                if ln == main and n == "bodo:query"]
    assert len(queries_) >= 2
    node_types = {"bodo:" + c for c in ("Aggregate", "Join", "ReadParquet",
                                        "Projection", "Filter", "Sort",
                                        "FromPandas")}
    inner = [s for s in spans if s[0] == main and
             (s[1].startswith("bodo:scan.") or s[1] in node_types)]
    assert inner
    for _, n, a, b, _ in inner:
        assert any(qa <= a and b <= qb for qa, qb in queries_), n


def test_no_session_and_tracing_off_is_the_old_no_op():
    assert config.tracing_level == 0 and not tracing.profiler_listening()
    with tracing.event("scan.column", pages=3) as ev:
        assert ev is None

    @tracing.traced_table_op
    def some_op():
        return None
    some_op()
    assert not tracing.has_events()
    assert tracing.query_agg() == {}


def test_ring_buffer_keeps_its_names(tmp_path, mesh8):
    """tracing_level 1, with and without a profiler session: the ring
    buffer and profile() carry the engine's own names, never `bodo:`."""
    import json
    ctx, sql = small_join_sql()
    set_config(tracing_level=1)

    def run():
        ctx.sql(sql).to_pandas()

    run()                           # the tables' nodes memoize
    tracing.reset()
    run()
    plain = {k for k in tracing.profile() if ":" not in k}
    assert {"Aggregate", "Join", "to_pandas", "query",
            "plan.optimize"} <= plain
    assert tracing.profile()["to_pandas"]["rows"] == 5
    tracing.reset()
    spans, _ = profiled(tmp_path, run)
    assert {k for k in tracing.profile() if ":" not in k} == plain
    events = json.loads(tracing.dump())["traceEvents"]
    assert events and not any(e["name"].startswith("bodo:") for e in events)
    # the same spans went to the profiler, under the prefix, with the id
    # of the query they belong to
    by_name = {s[1]: s[4] for s in spans}
    assert {"bodo:" + k for k in plain} <= set(by_name)
    assert by_name["bodo:Aggregate"]["query_id"] in tracing.query_ids()


# ---------------------------------------------------------- program names
def taxi_shape(tmp_path):
    trips, weather = taxi_files(tmp_path, rows=30000)
    return lambda: taxi_wl.frontend_pipeline(trips, weather)


def tpch_shape(number):
    def make(_tmp_path):
        ctx = bodo_tpu.sql.BodoSQLContext(gen_tpch(n_orders=2000, seed=1))
        return lambda: ctx.sql(QUERIES[number]).to_pandas()
    return make


@pytest.mark.parametrize("devices", [1, 4], ids=["replicated", "mesh4"])
@pytest.mark.parametrize("shape,joins", [
    (taxi_shape, True), (tpch_shape(5), True), (tpch_shape(1), False)],
    ids=["taxi", "tpch_q5", "tpch_q1"])
def test_every_program_is_named_for_its_operator(tmp_path, shape, joins,
                                                 devices):
    # small sources shard too, so that the mesh runs the sharded programs
    set_config(shard_min_rows=1000 if devices > 1 else 1 << 40)
    with mesh_mod.use_mesh(bodo_tpu.make_mesh(jax.devices()[:devices])):
        query = shape(tmp_path)
        query()                     # compile outside the trace
        _, modules = profiled(tmp_path / "trace", query)
    assert modules
    generic = sorted(m for m in modules if GENERIC.match(m))
    assert not generic, generic
    assert any(re.search(r"groupby|fusedagg", m) for m in modules), \
        sorted(modules)
    if joins:
        assert any("join" in m for m in modules), sorted(modules)
    else:
        assert not any("join" in m for m in modules), sorted(modules)
    # only an aggregate stage may say fusedagg, only a join may say join:
    # the benchmark's family patterns count on it
    for m in modules:
        if "fusedagg" in m:
            assert m == "jit_fusedagg", m


def test_named_jit_names_the_program():
    from bodo_tpu.utils.kernel_cache import named_jit

    def body(x):
        return x + 1
    fn = named_jit("groupby_dense", body)  # shardcheck: ignore[unregistered-jit]
    assert "@jit_groupby_dense" in fn.lower(np.ones(4)).as_text()
    assert fn(np.ones(4)).tolist() == [2.0] * 4


def test_dense_route_rides_on_the_spans(tmp_path, mesh8):
    """What `tracing.annotate` adds below a span arrives as TraceMe
    metadata of that span, with tracing off: the fused aggregate's and
    the dense groupby's route."""
    from bodo_tpu import Table
    from bodo_tpu import relational as R
    assert config.tracing_level == 0
    ctx = bodo_tpu.sql.BodoSQLContext(gen_tpch(n_orders=2000, seed=1))
    df = pd.DataFrame({"k": np.arange(4000) % 7, "v": np.arange(4000.0)})

    def queries():
        ctx.sql(QUERIES[1]).to_pandas()
        R.groupby_agg(Table.from_pandas(df), ["k"],
                      [("v", "sum", "s"), ("v", "var", "vv")])

    with mesh_mod.use_mesh(bodo_tpu.make_mesh(jax.devices()[:1])):
        spans, _ = profiled(tmp_path, queries)
    by_name = {s[1]: s[4] for s in spans}
    assert by_name["bodo:fused_group"]["dense_route"] == "reduce"
    assert by_name["bodo:groupby_agg"]["dense_route"] == "scatter"
    assert by_name["bodo:groupby_agg"]["rows"] == 7
