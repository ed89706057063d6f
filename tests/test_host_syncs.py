"""Every mid-query read of a device value goes through one counted entry
point, `utils/tracing.py host_read`, which writes the span
`bodo:sync.<site>` the benchmark's `host_syncs` and `sync_idle_ms` read;
the executor's own work between operators is `bodo:exec.*`, which
`exec_host_ms` reads. These tests hold the engine to that on the CPU:
no device value reaches the host any other way while small versions of
the benchmark's queries run (the answer's own copy in `Table.to_pandas`
excepted), the per-site counts of Q1 and Q5 are pinned, and the spans
reach both the ring buffer and a profiler session.

A read of a `jax.Array` goes through `ArrayImpl._value` (`int`, `bool`,
`.tolist()`, `.item()`, `jax.device_get`) or `ArrayImpl.__array__`
(`np.asarray`); the fixture wraps both. Transfer guards do not fire on
the CPU backend, so they cannot stand in.
"""

import contextlib
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

import bodo_tpu
from bodo_tpu.config import config, set_config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.utils import tracing

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import spec  # noqa: E402

STRUCTURE = {"structure_seed": 20260926}
# (query, generator, scale, devices): the cells' queries at the sizes of
# their own tests; on four devices, as tests/test_tpch_q5_4chip_cell.py,
# a table shards from 1,000 rows
CASES = {
    "tpch_q1": ("tpch_q1", "tpch", {"orders": 3000}, 1),
    "tpch_q5": ("tpch_q5", "tpch", {"orders": 3000}, 1),
    "tpch_q9": ("tpch_q9", "tpch_parts", {"orders": 3000}, 1),
    "tpch_q18": ("tpch_q18", "tpch_volume", {"orders": 60000}, 1),
    "taxi": ("taxi_weather", "taxi", {"rows": 20000}, 1),
    "tpch_q5_4dev": ("tpch_q5", "tpch", {"orders": 20000}, 4),
}
# a query's host reads by site, the second run of a compiled query
PINNED = {
    "tpch_q1": {"fused_group_count": 1},
    "tpch_q5": {"build_dup": 9, "filter_count": 1, "fused_count": 1,
                "group_count": 1, "join_overflow": 2, "key_range": 10,
                "probe_count": 5, "probe_unresolved": 2},
}


@pytest.fixture(autouse=True)
def _engine_state():
    old = (config.result_cache, config.shard_min_rows, config.tracing_level)
    set_config(result_cache=False)
    tracing.reset()
    yield
    set_config(result_cache=old[0], shard_min_rows=old[1],
               tracing_level=old[2])
    tracing.reset()


class Reads:
    """Device values read on the host outside `host_read` and
    `Table.to_pandas` while `watching()`: the innermost engine frame of
    each, as `file:line:function`."""

    def __init__(self):
        self.outside = []
        self._on = False

    @contextlib.contextmanager
    def watching(self):
        self._on = True
        try:
            yield
        finally:
            self._on = False

    def note(self):
        if not self._on:
            return
        f = sys._getframe(2)
        site = None
        while f is not None:
            code = f.f_code
            path = code.co_filename.replace(os.sep, "/")
            if code.co_name == "host_read" and \
                    path.endswith("bodo_tpu/utils/tracing.py"):
                return
            if code.co_name == "to_pandas" and \
                    path.endswith("bodo_tpu/table/table.py"):
                return
            if site is None and "/bodo_tpu/" in path:
                site = (f"{path.split('/bodo_tpu/')[-1]}:{f.f_lineno}:"
                        f"{code.co_name}")
            f = f.f_back
        self.outside.append(site or "(outside the engine)")


@pytest.fixture
def reads(monkeypatch):
    from jax._src import array
    impl = array.ArrayImpl
    got = Reads()
    value, to_array = impl._value, impl.__array__

    def _value(self):
        got.note()
        return value.fget(self)

    def __array__(self, *a, **k):
        got.note()
        return to_array(self, *a, **k)

    monkeypatch.setattr(impl, "_value", property(_value))
    monkeypatch.setattr(impl, "__array__", __array__)
    return got


def cell_query(case, tmp_path):
    """(run, devices): one execution of the case's query to its answer
    in host memory, as `benchmarks/harness/engine.py` runs it."""
    name, gen, scale, devices = CASES[case]
    query = spec.Query(name)
    inputs = spec.load_module("gen", gen).generate(
        {**scale, **STRUCTURE}, 1, str(tmp_path))
    set_config(shard_min_rows=1000 if devices > 1 else 1 << 40)
    if query.entry == "sql":
        from bodo_tpu.sql import BodoSQLContext
        ctx = BodoSQLContext(inputs["frames"])
        return (lambda: ctx.sql(query.text).to_pandas()), devices
    import bodo_tpu.pandas_api as bd
    return (lambda: query.module.collect(query.module.plan(bd, inputs)),
            devices)


def on_devices(n):
    return mesh_mod.use_mesh(bodo_tpu.make_mesh(jax.devices()[:n]))


def syncs_of(run):
    before = tracing.sync_counts()
    run()
    after = tracing.sync_counts()
    return {k: n - before.get(k, 0) for k, n in after.items()
            if n - before.get(k, 0)}


# -------------------------------------------------------- completeness
@pytest.mark.parametrize("case", sorted(CASES))
def test_no_device_value_reaches_the_host_unnamed(case, reads, tmp_path,
                                                  mesh8):
    run, devices = cell_query(case, tmp_path)
    with on_devices(devices):
        with reads.watching():
            run()               # compiles, builds, places the tables
            syncs = syncs_of(run)
    assert reads.outside == [], sorted(set(reads.outside))
    # and the query did read device values, through host_read
    assert sum(syncs.values()) >= 1, syncs


# ------------------------------------------------------ per-site counts
@pytest.mark.parametrize("case", sorted(PINNED))
def test_per_site_counts_are_pinned(case, tmp_path, mesh8):
    run, devices = cell_query(case, tmp_path)
    with on_devices(devices):
        run()
        first = syncs_of(run)
        again = syncs_of(run)
    assert first == again == PINNED[case], first


# ------------------------------------------------------------ host_read
def test_host_read_off_is_device_get_and_opens_no_event(monkeypatch):
    assert config.tracing_level == 0 and not tracing.profiler_listening()
    opened = []
    real = tracing.event

    def event(name, **args):
        opened.append(name)
        return real(name, **args)
    monkeypatch.setattr(tracing, "event", event)
    tree = {"n": jax.numpy.arange(5), "flag": (jax.numpy.asarray(True),
                                               None)}
    before = tracing.sync_counts()
    got = tracing.host_read("probe_count", tree)
    want = jax.device_get(tree)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    np.testing.assert_array_equal(got["n"], want["n"])
    assert bool(got["flag"][0]) and got["flag"][1] is None
    after = tracing.sync_counts()
    # one call is one sync, whatever the number of leaves
    assert after["probe_count"] - before.get("probe_count", 0) == 1
    assert {k for k in after if after[k] != before.get(k, 0)} \
        == {"probe_count"}
    assert opened == [] and not tracing.has_events()


# ----------------------------------------------------------------- spans
def test_ring_buffer_holds_sync_and_exec_events(tmp_path, mesh8):
    run, devices = cell_query("tpch_q5", tmp_path)
    with on_devices(devices):
        run()
        set_config(tracing_level=1)
        tracing.reset()
        syncs = syncs_of(run)
    events = json.loads(tracing.dump())["traceEvents"]
    sync = [e for e in events if e["name"].startswith("sync.")]
    execs = {e["name"] for e in events if e["name"].startswith("exec.")}
    assert sorted(e["name"][len("sync."):] for e in sync) == sorted(
        s for s, n in syncs.items() for _ in range(n))
    # Q5's five joins are one inner chain, re-planned once its leaves ran
    assert execs == {"exec.observe", "exec.replan"}
    qids = {e["args"].get("query_id") for e in events
            if e["name"].startswith(("sync.", "exec."))}
    assert len(qids) == 1 and None not in qids
    assert qids <= set(tracing.query_ids())


def test_profiler_holds_sync_and_exec_spans(tmp_path, mesh8):
    run, devices = cell_query("tpch_q5", tmp_path / "data")
    with on_devices(devices):
        run()
        before = tracing.sync_counts()
        spans, _ = profiled(tmp_path / "trace", run)
        after = tracing.sync_counts()
    assert config.tracing_level == 0 and not tracing.has_events()
    names = [s[1] for s in spans]
    want = sorted("bodo:sync." + k for k, n in after.items()
                  for _ in range(n - before.get(k, 0)))
    assert want and \
        sorted(n for n in names if n.startswith("bodo:sync.")) == want
    assert {"bodo:exec.observe", "bodo:exec.replan"} <= set(names)
    # a sync lies inside the query that made it, an exec span too
    queries = [(a, b) for _, n, a, b, _ in spans if n == "bodo:query"]
    for _, n, a, b, _ in spans:
        if n.startswith(("bodo:sync.", "bodo:exec.")):
            assert any(qa <= a and b <= qb for qa, qb in queries), n
    # the re-plan's span holds no node's span: the leaves ran before it
    replan = [(a, b) for _, n, a, b, _ in spans if n == "bodo:exec.replan"]
    nodes = [(a, b) for _, n, a, b, _ in spans
             if n in ("bodo:FromPandas", "bodo:Filter", "bodo:Projection")]
    assert not any(ra <= a and b <= rb for ra, rb in replan
                   for a, b in nodes)


def test_explain_analyze_shows_syncs_per_operator(tmp_path, mesh8):
    from bodo_tpu.plan import explain
    from bodo_tpu.sql import BodoSQLContext
    inputs = spec.load_module("gen", "tpch").generate(
        {**CASES["tpch_q5"][2], **STRUCTURE}, 1)
    ctx = BodoSQLContext(inputs["frames"])
    text = spec.Query("tpch_q5").text
    with on_devices(1):
        ctx.sql(text).to_pandas()
        set_config(tracing_level=1)
        before = tracing.sync_counts()
        tree = ctx.explain_analyze(text)
        after = tracing.sync_counts()
    total = sum(after.values()) - sum(before.values())
    # a node's syncs are inclusive, as its wall is: the root holds all
    syncs = [int(x) for x in re.findall(r"xla=\S*syncs=(\d+)", tree)]
    assert syncs and max(syncs) == total, tree
    records = explain._queries[explain.last_query_id()]["records"]
    joins = [r for r in records.values() if r["op"] == "Join"]
    assert joins and all(r["xla"]["syncs"] >= 1 for r in joins), joins
